"""The ``long_seq_epochs`` cell's own parts: the operation count of
``harness/flops_hybrid.py`` by hand, the four readers this cell brings on
a hand-built trace and hand-set counters, and a traced rehearsal (the
bfloat16 control refused, ``moe_dropped`` read from the program's
counters)."""

import types

import numpy as np
import pytest

from benchmark.harness import flops_hybrid, spec, xplane
from benchmark.harness.xplane import Event
from conftest import run_cell

CELL = "kimi_linear_48b.long_seq_epochs"
SIZES = {"hidden": 4, "layers": (("kda", "dense"), ("kda", "moe"),
                                 ("mla", "moe")),
         "kda_heads": 2, "kda_dim": 3, "conv_kernel": 4, "gate_rank": 5,
         "mla_heads": 2, "kv_rank": 6, "nope": 3, "rope": 2, "v_dim": 3,
         "ffn": 7, "experts": 16, "expert_ffn": 5, "shared": 1, "vocab": 11}


def test_model_operations_by_hand():
    h, a = 4, 6
    kda = 2 * (3 * h * a + 2 * (h * 5 + 5 * a) + h * 2 + a * h) \
        + 2 * 3 * a * 4 + 7 * 2 * 3 * 3
    mla = 2 * (h * 2 * 5 + h * 8 + 6 * 2 * 6 + 2 * 3 * h)
    dense, routed = 2 * 3 * h * 7, 2 * (h * 16 + 3 * h * 5)
    token = (kda + dense) + (kda + routed) + (mla + routed)
    assert flops_hybrid.per_token(**SIZES) == token
    n = 10
    attention = 2 * (3 + 2 + 3) * n * (n + 1)      # causal half, 1 layer
    head = 2 * h * 11 * (n - 1)
    assert flops_hybrid.hybrid_forward(n, **SIZES) == \
        token * n + attention + head
    assert flops_hybrid.hybrid_forward(0, **SIZES) == 0
    # an assignment a held expert received: one SwiGLU at the expert width
    assert flops_hybrid.hybrid_step([n, 0], 6.0, **SIZES) == 3 * (
        flops_hybrid.hybrid_forward(n, **SIZES) + 6 * 2 * 3 * h * 5)


def test_the_cut_costs_about_two_gigaflop_a_token():
    """Kimi-Linear's share as cut: 2.15 GFLOP a valid token forward +
    backward at n = 3,072 with a quarter of an assignment a token a
    routed layer; four of its five layers are KDA."""
    real = flops_hybrid.hybrid_sizes(spec.Cell(CELL).config)
    assert [m for m, _ in real["layers"]] == ["kda"] * 3 + ["mla", "kda"]
    assert [f for _, f in real["layers"]] == ["dense"] + ["moe"] * 4
    per_token = flops_hybrid.hybrid_step([3072], 3072 * 4 * 8 * 8 / 256,
                                         **real) / 3072
    assert 2.1e9 < per_token < 2.2e9
    # the routed experts do little here: under 2% of a token's work
    routed = 3 * 2 * 3 * 2304 * 1024 * 4 * 0.25
    assert routed / per_token < 0.02


def ev(name, a, b):
    return Event(name, float(a), float(b), name)


def traced_run(scopes, stats, geometry=None):
    trace = {
        "/device:TPU:0": {
            xplane.OPS_LINE: [
                ev("while.1", 100, 500),      # the KDA scan ...
                ev("fusion.7", 150, 300),     # ... and an op of its body
                ev("fusion.8", 500, 600),     # latent attention
                ev("ragged-dot.2", 600, 640),     # routed experts
                ev("fusion.9", 640, 700),     # Adam: no tower scope
                ev("fusion.7", 1500, 1600)],  # outside the step
            xplane.MODULES_LINE: [ev("jit_step(1)", 100, 900)]},
        xplane.HOST_PLANE: {"main": [ev("bench.window", 0, 1000)]}}
    run = types.SimpleNamespace(
        measured=types.SimpleNamespace(scopes=scopes), trace=trace,
        trace_window=(0.0, 1000.0), chips=1, stats=stats,
        geometry=geometry or {})
    run.step_runs = lambda plane: xplane.module_runs(
        trace, plane, run.trace_window, "jit_step")
    return run


SCOPES = {"while.1": "jit(step)/transpose(jvp(checkpoint))/tower.kda/while",
          "fusion.7": "jit(step)/jvp(checkpoint)/tower.kda/while/body/mul",
          "fusion.8": "jit(step)/jvp(checkpoint)/tower.mla/checkpoint/exp",
          "ragged-dot.2": "jit(step)/jvp(tower.moe)/experts/ragged_dot"}


def reader(name):
    return spec.load_module("layer_metrics", name).read


@pytest.mark.parametrize("name,want", [
    ("tower.kda_share", 50.0),      # [100, 500] united, of an 800 ns step
    ("tower.mla_share", 12.5), ("tower.moe_share", 5.0),
    ("tower.device_share", 67.5)])
def test_scope_shares_on_a_hand_built_trace(name, want):
    run = traced_run(SCOPES, {})
    assert reader(name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["tower.kda_share", "tower.mla_share",
                                  "tower.moe_share",
                                  "tower.moe_load_imbalance"])
def test_a_program_without_the_scopes_or_counters_leaves_the_metric_out(
        name):
    """The parent's program has no such scope and no such counter: the
    reader returns nothing and does not raise."""
    other = {"fusion.9": "jit(step)/tower.ut/while"}
    for run in (traced_run(other, {}), traced_run(None, {}),
                traced_run({}, {"tower.tokens_valid": 5.0})):
        assert reader(name)(run) is None
    bare = types.SimpleNamespace(measured=types.SimpleNamespace(),
                                 trace=None, trace_window=None, chips=1,
                                 stats={}, geometry={})
    assert reader(name)(bare) is None


@pytest.mark.parametrize("top,mean,want", [
    (64.0, 40.0, 1.6),     # two passes: busiest 30 + 34, mean 20 + 20
    (0.0, 0.0, None),      # no held expert received a token: nothing to read
    (None, None, None)])
def test_load_imbalance_from_the_whole_runs_counters(top, mean, want):
    """What the window's counters alone say is not read: a router may
    have turned away from this chip by then."""
    run = traced_run(SCOPES, {"tower.moe.expert_load_max": 9.0,
                              "tower.moe.expert_load_mean": 1.0},
                     {"moe_run_load_max": top, "moe_run_load_mean": mean})
    got = reader("tower.moe_load_imbalance")(run)
    assert got == (want if want is None else pytest.approx(want))


def test_a_traced_rehearsal_refuses_the_control_and_counts_the_routing():
    rc, result, err = run_cell(
        ["--workload", CELL, "--seed", "4", "--seconds", "1", "--trace", "1",
         "--rehearse"])
    assert rc == 0, err[-2000:]
    checks = result["detail"]["checks"]
    check = checks["reference_losses"]
    assert result["correct"] and check["ok"] and checks["feed_planes"]["ok"]
    control = check["control"]
    assert control["ok"] is False and len(control["losses"]) == 2
    assert all(np.isfinite(control["losses"]))
    mine, its = check["update_error"], control["update_error"]
    assert mine["dense"] < 1e-3 and mine["rows"] < 1e-3
    assert its["dense"] > check["update_rtol"]["dense"] > 10 * mine["dense"]
    # every leaf is compared, the routed layers' and the decays' too
    for leaf in ("layers.1.ffn.router", "layers.1.ffn.wg",
                 "layers.0.mixer.a_log", "layers.3.mixer.wkvb", "rows.mf"):
        assert leaf in mine["by_leaf"]
    moe = checks["moe_dropped"]
    assert moe["ok"] and moe["dropped_assignments"] == 0
    assert 0 <= moe["assignments_held_compared_epoch"] \
        <= moe["assignments_held"] < moe["assignments"]
    assert moe["assignments_held"] > 0
    geometry = result["detail"]["geometry"]
    assert geometry["model_flops_per_step"] > 0
    assert geometry["moe_assignments_held_per_step"] > 0
    assert geometry["moe_run_load_max"] \
        >= geometry["moe_run_load_mean"] > 0
    for name in ("step.mfu", "tower.device_share", "tower.kda_share",
                 "tower.mla_share", "tower.moe_share"):  # no device plane
        assert name not in result["metrics"]
    assert result["metrics"]["tower.padding_share"]["unit"] == "%"
    assert result["metrics"]["tower.moe_load_imbalance"]["unit"] == "ratio"
