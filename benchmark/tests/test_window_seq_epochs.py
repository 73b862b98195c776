"""The ``window_seq_epochs`` cell's own parts: the operation count of
``harness/flops_sambay.py`` by hand, the layer kinds of the cut, the four
readers this cell brings on a hand-built trace, the generator's own
layout of a working set that holds every head key, and a traced
rehearsal (the bfloat16 control refused, ``tied_head`` read from the
program's counters)."""

import types

import numpy as np
import pytest

from benchmark.generators import window_seq_epochs
from benchmark.harness import flops_sambay, spec, xplane
from benchmark.harness.xplane import Event
from conftest import run_cell

CELL = "phi4_mini_flash.window_seq_epochs"
SIZES = {"hidden": 4, "kinds": ("mamba", "swa", "mamba", "attn_full", "gmu",
                                "attn_cross"),
         "heads": 4, "kv_heads": 2, "head_dim": 3, "window": 4, "ffn": 7,
         "d_inner": 8, "d_state": 2, "dt_rank": 3, "conv_kernel": 4,
         "vocab": 11}


def test_model_operations_by_hand():
    h, a, k, di, ns, r = 4, 12, 6, 8, 2, 3
    mamba = 2 * (h * 2 * di + di * (r + 2 * ns) + r * di + di * h) \
        + 2 * di * 4 + 7 * di * ns
    gmu = 2 * (h * di + di * h)
    attn, cross = 2 * (h * (a + 2 * k) + a * h), 2 * (h * a + a * h)
    mlp = 2 * 3 * h * 7
    token = 2 * mamba + 2 * attn + gmu + cross + 6 * mlp
    assert flops_sambay.per_token(**SIZES) == token
    n = 10
    pair = 4 * 4 * 3                     # both maps of every head
    full = n * (n + 1) / 2               # the causal half
    window = 4 * 5 / 2 + (n - 4) * 4     # the window's keys only
    assert flops_sambay.attention_pairs(n) == full
    assert flops_sambay.attention_pairs(n, 4) == window
    assert flops_sambay.attention_pairs(3, 4) == 6
    head = 2 * h * 11 * (n - 1)
    assert flops_sambay.sambay_forward(n, **SIZES) == \
        token * n + pair * (2 * full + window) + head
    assert flops_sambay.sambay_forward(0, **SIZES) == 0
    assert flops_sambay.sambay_step([n, 0, 3], **SIZES) == 3 * (
        flops_sambay.sambay_forward(n, **SIZES)
        + flops_sambay.sambay_forward(3, **SIZES))


def test_the_cut_is_published_layers_14_to_19_at_their_widths():
    cfg = spec.Cell(CELL).config
    assert flops_sambay.layer_kinds(cfg) == (
        (14, "mamba"), (15, "swa"), (16, "mamba"), (17, "attn_full"),
        (18, "gmu"), (19, "attn_cross"))
    whole = {**cfg, "num_hidden_layers": 32,
             "share": {**cfg["share"], "layer_first": 0}}
    kinds = [k for _, k in flops_sambay.layer_kinds(whole)]
    assert [kinds.count(k) for k in ("mamba", "swa", "attn_full", "gmu",
                                     "attn_cross")] == [9, 8, 1, 7, 7]
    real = flops_sambay.sambay_sizes(cfg)
    assert (real["d_inner"], real["head_dim"], real["vocab"]) == \
        (5120, 64, 25008)
    # 633M parameters in the layers: 2 x 633M x 3 a token without the
    # scan, attention and head; 4.3-4.6 GFLOP a valid token at n = 6,144
    per_token = flops_sambay.sambay_step([6144], **real) / 6144
    assert 4.3e9 < per_token < 4.6e9
    # at 16x the window the window layer's pairs are 1/6 of a full one's
    assert flops_sambay.attention_pairs(8192, 512) * 8.2 \
        < flops_sambay.attention_pairs(8192)


def ev(name, a, b):
    return Event(name, float(a), float(b), name)


def traced_run(scopes):
    trace = {
        "/device:TPU:0": {
            xplane.OPS_LINE: [
                ev("while.1", 100, 300),      # a Mamba segment scan ...
                ev("fusion.7", 150, 250),     # ... and an op of its body
                ev("fusion.8", 300, 340),     # window attention
                ev("while.2", 340, 500),      # full attention's blocks
                ev("fusion.9", 500, 520),     # the GMU
                ev("while.3", 520, 600),      # cross attention's blocks
                ev("fusion.10", 600, 700),    # Adam: no tower scope
                ev("fusion.7", 1500, 1600)],  # outside the step
            xplane.MODULES_LINE: [ev("jit_step(1)", 100, 900)]},
        xplane.HOST_PLANE: {"main": [ev("bench.window", 0, 1000)]}}
    run = types.SimpleNamespace(
        measured=types.SimpleNamespace(scopes=scopes), trace=trace,
        trace_window=(0.0, 1000.0), chips=1, stats={}, geometry={})
    run.step_runs = lambda plane: xplane.module_runs(
        trace, plane, run.trace_window, "jit_step")
    return run


SCOPES = {
    "while.1": "jit(step)/transpose(jvp())/while/body/checkpoint/tower.mamba/"
               "while",
    "fusion.7": "jit(step)/jvp()/while/body/closed_call/tower.mamba/mul",
    "fusion.8": "jit(step)/jvp()/while/body/checkpoint/tower.swa/while/exp",
    "while.2": "jit(step)/transpose(jvp(tower.attn_full))/while",
    "fusion.9": "jit(step)/jvp()/while/body/checkpoint/tower.gmu/dot_general",
    "while.3": "jit(step)/jvp()/while/body/checkpoint/tower.attn_cross/while"}


def reader(name):
    return spec.load_module("layer_metrics", name).read


@pytest.mark.parametrize("name,want", [
    ("tower.mamba_share", 25.0),    # [100, 300] united, of an 800 ns step
    ("tower.swa_share", 5.0),
    ("tower.global_attn_share", 30.0),      # full 160 + cross 80
    ("tower.gmu_share", 2.5), ("tower.device_share", 62.5)])
def test_scope_shares_on_a_hand_built_trace(name, want):
    assert reader(name)(traced_run(SCOPES)) == pytest.approx(want)


def test_global_attention_is_read_where_only_one_of_its_scopes_ran():
    only_full = {k: v for k, v in SCOPES.items() if k != "while.3"}
    assert reader("tower.global_attn_share")(traced_run(only_full)) == \
        pytest.approx(20.0)


@pytest.mark.parametrize("name", ["tower.mamba_share", "tower.swa_share",
                                  "tower.global_attn_share",
                                  "tower.gmu_share"])
def test_a_program_without_the_scopes_leaves_the_metric_out(name):
    """The parent's program has no such scope: the reader returns nothing
    and does not raise."""
    other = {"fusion.9": "jit(step)/tower.kda/while"}
    for run in (traced_run(other), traced_run(None), traced_run({})):
        assert reader(name)(run) is None
    bare = types.SimpleNamespace(measured=types.SimpleNamespace(),
                                 trace=None, trace_window=None, chips=1,
                                 stats={}, geometry={})
    assert reader(name)(bare) is None


def test_the_generators_layout_ranks_keys_among_the_pass_and_the_head():
    drawn = {"lens": np.array([[3], [2]]), "keys": np.array([9, 4, 9, 30, 2]),
             "labels": np.array([1, 0])}
    head = np.arange(1, 9)               # ids 0..7 at key_base 1
    own = window_seq_epochs.feed_planes(drawn, 1, 4, head)
    # held keys 1..8, 9, 30: key k <= 9 sits at row k, key 30 at row 10
    np.testing.assert_array_equal(own["indices"][:, 0, :, 0],
                                  [[9, 4, 9, 0], [10, 2, 0, 0]])
    np.testing.assert_array_equal(own["seq_keys"][:, 0],
                                  [[9, 4, 9, 0], [30, 2, 0, 0]])
    np.testing.assert_array_equal(own["head_rows"],
                                  np.tile(np.arange(1, 9), (2, 1)))
    feed = types.SimpleNamespace(data=dict(own))
    assert window_seq_epochs.check_feed_planes(feed, own)["ok"]
    feed.data["head_rows"] = own["head_rows"][:, ::-1]
    assert window_seq_epochs.check_feed_planes(feed, own)["differing"] == \
        ["head_rows"]
    del feed.data["head_rows"]           # the parent's feed has no such plane
    assert not window_seq_epochs.check_feed_planes(feed, own)["ok"]


@pytest.mark.parametrize("trim,want", [
    (0, np.sqrt((9 + 1 + 4) / (16 + 4 + 16))),    # every row
    (1, np.sqrt((1 + 4) / (4 + 16))),             # without the worst
    (2, np.sqrt(1 / 4)), (3, 0.0)])
def test_trimmed_error_leaves_the_worst_rows_out_of_both_sums(trim, want):
    err = np.array([0.0, 9.0, 1.0, 4.0])
    moved = np.array([0.0, 16.0, 4.0, 16.0])
    got = window_seq_epochs.trimmed_error(err, moved, trim)
    assert got == pytest.approx(want)
    # a fault in every row does not hide under the trim
    assert window_seq_epochs.trimmed_error(moved, moved, 1) == 1.0


def test_the_first_push_is_kept_for_the_control():
    calls = []
    ref = types.SimpleNamespace(
        push_rows=lambda *a: calls.append(a) or "pushed", other=7)
    keeps = window_seq_epochs.KeepsFirstPush(ref)
    assert keeps.other == 7 and keeps.first is None
    assert keeps.push_rows("r", "b", "d0", "s") == "pushed"
    assert keeps.push_rows("r", "b", "d1", "s") == "pushed"
    assert keeps.first == ("r", "b", "d0", "s") and len(calls) == 2


def test_a_traced_rehearsal_refuses_the_control_and_counts_the_head():
    rc, result, err = run_cell(
        ["--workload", CELL, "--seed", "4", "--seconds", "1", "--trace", "1",
         "--rehearse"])
    assert rc == 0, err[-2000:]
    checks = result["detail"]["checks"]
    check = checks["reference_losses"]
    assert result["correct"] and check["ok"] and checks["feed_planes"]["ok"]
    assert "head_rows" in checks["feed_planes"]["planes"]
    control = check["control"]
    assert control["ok"] is False and len(control["losses"]) == 2
    assert all(np.isfinite(control["losses"]))
    mine, its = check["update_error"], control["update_error"]
    assert mine["dense"] < 1e-3 and mine["rows"] < 1e-3
    assert its["dense"] > check["update_rtol"]["dense"] > 10 * mine["dense"]
    # every leaf is compared: each kind of mixer, the norms' biases, the
    # rows; and no leaf is a head
    for leaf in ("layers.0.mixer.a_log", "layers.1.mixer.lq1",
                 "layers.3.mixer.wqkv", "layers.4.mixer.w1",
                 "layers.5.mixer.bo", "layers.2.ln1_b", "lnf_b", "rows.mf"):
        assert leaf in mine["by_leaf"]
    assert not any("head" in leaf for leaf in mine["by_leaf"])
    # where the rows' error lies, row by row: the worst first, each with
    # its places in batch 0
    profile = check["rows_profile"]
    worst = profile["worst_rows"]
    assert worst and all(w["shows_in_step"] == len(w["at"]) or
                         len(w["at"]) == 3 for w in worst if w["at"])
    assert worst == sorted(worst, key=lambda w: -w["error_sq"])
    assert profile["error_sq"] >= worst[0]["error_sq"] >= 0
    # the rows' second limit, and the control that proves the gate sees
    # the head's merge: left out, it is refused in every run
    trimmed = profile["trimmed"]
    assert trimmed["rows"] == 2 and 0 <= trimmed["error"] <= mine["rows"]
    left_out = check["head_left_out"]
    assert left_out["refused"] and left_out["rows"] > 0.3
    assert left_out["rows_trimmed"] > 10 * trimmed["limit"]
    tied = checks["tied_head"]
    vocab = spec.Cell(CELL).sized(True)["vocab_size"]
    assert tied["ok"] and tied["head_rows_a_step"] == vocab
    assert 0 < tied["head_rows_applied"] < tied["head_rows_read"]
    assert not tied["leaves_as_wide_as_the_vocabulary"]
    geometry = result["detail"]["geometry"]
    assert geometry["model_flops_per_step"] > 0
    assert 0 < geometry["head_rows_applied_share"] < 1
    assert geometry["table_rows"] > vocab   # every head key is held
    assert len(geometry["epoch_seconds"]) == result["detail"]["units"]
    assert geometry["window_host_s"]["trainer.train_pass"] > 0
    for name in ("step.mfu", "tower.device_share", "tower.mamba_share",
                 "tower.swa_share", "tower.global_attn_share",
                 "tower.gmu_share"):         # no device plane on the CPU
        assert name not in result["metrics"]
    assert result["metrics"]["tower.padding_share"]["unit"] == "%"
