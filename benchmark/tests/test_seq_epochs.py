"""The ``seq_epochs`` generator's own parts: its pass writer against the
program's parser, its own layout of the pass, the operation count of
``harness/flops.py`` by hand, the gate, and the bfloat16 control that a
traced run puts through it."""

import numpy as np
import pytest

from benchmark.generators import seq_epochs
from benchmark.harness import flops, slotdata, spec
from conftest import run_cell

CELL = "ouro_2p6b.seq_epochs"


def test_pass_writer_counts_what_it_writes(tmp_path):
    cfg = spec.Cell(CELL).sized(True)
    fields = slotdata.Fields(cfg)
    meta = seq_epochs.write_pass(str(tmp_path), fields, 3, 12)
    keys, lens = [], []
    for path in meta["files"]:
        for line in open(path):
            tok = line.split()
            assert tok[0] == "1" and tok[2] == "1"      # one label, one dense
            n = int(tok[4])
            assert len(tok) == 5 + n
            lens.append(n)
            keys += [int(k) for k in tok[5:]]
    assert len(lens) == 12 and sum(lens) == meta["stats"]["occurrences"]
    assert max(lens) == meta["stats"]["max_slot_len"] <= cfg["lengths"]["max"]
    assert min(lens) >= cfg["lengths"]["min"]
    assert len(set(keys)) == meta["stats"]["unique_keys"]
    assert 1 <= min(keys) and max(keys) <= cfg["fields"]["vocab"][0]
    for k, c in zip(meta["probe"]["keys"], meta["probe"]["counts"]):
        assert keys.count(k) == c


def test_model_operations_by_hand():
    sizes = {"hidden": 4, "heads": 2, "head_dim": 3, "ffn": 5, "layers": 2,
             "steps": 3, "vocab": 7}
    a = 6
    layer = 2 * (4 * 4 * a + 3 * 4 * 5) * 10 + 2 * a * 10 * 11
    head = 2 * 4 * 8 * 9
    assert flops.looplm_forward(10, **sizes) == 3 * (2 * layer + head)
    assert flops.looplm_forward(0, **sizes) == 0
    assert flops.looplm_step([10, 0], **sizes) == \
        3 * flops.looplm_forward(10, **sizes)
    # Ouro-2.6B's cut: 12.6 GFLOP a token of forward + backward at n = 768
    real = flops.looplm_sizes(spec.Cell(CELL).config)
    per_token = flops.looplm_step([768], **real) / 768
    assert 12.0e9 < per_token < 13.5e9


def test_feed_planes_lay_the_pass_out_by_hand():
    """Two batches of two sequences, capacity 4: keys by place, rows by
    rank among the pass's keys (row 0 reserved), zeros past a length."""
    drawn = {"lens": np.array([[2], [4], [1], [3]]),
             "keys": np.array([7, 3, 9, 9, 3, 50, 8, 7, 7, 2]),
             "labels": np.array([1, 0, 0, 1])}
    own = seq_epochs.feed_planes(drawn, 2, 4)
    assert own["seq_keys"].tolist() == [[[7, 3, 0, 0], [9, 9, 3, 50]],
                                        [[8, 0, 0, 0], [7, 7, 2, 0]]]
    # unique keys 2 3 7 8 9 50 -> rows 1..6; indices is [N, S, L, B]
    assert own["indices"].shape == (2, 1, 4, 2)
    assert own["indices"][0, 0].T.tolist() == [[3, 2, 0, 0], [5, 5, 2, 6]]
    assert own["indices"][1, 0].T.tolist() == [[4, 0, 0, 0], [3, 3, 1, 0]]
    assert own["lengths"].tolist() == [[[2, 4]], [[1, 3]]]
    assert own["labels"].tolist() == [[1.0, 0.0], [0.0, 1.0]]
    assert own["valid"].all() and own["valid"].shape == (2, 2)


def gate(losses=(2.0, 1.9), moved=None):
    ref = seq_epochs.LoopReferenceCheck.__new__(seq_epochs.LoopReferenceCheck)
    ref.rtol = 5e-4
    ref.update_rtol = {"dense": 0.02, "rows": 0.15, "leaf": 0.5}
    leaves = {"head": np.array([1.0, 2.0, 3.0], np.float32),
              "gate_b": np.float32(0.5),
              "rows.mf": np.array([[0.25, 0.5]], np.float32)}
    ref.want = {"losses": list(losses), "leaves": leaves,
                "moved": moved or {"head": 1e-2, "gate_b": 1e-6,
                                   "rows.mf": 1e-4}}
    return ref, leaves


@pytest.mark.parametrize("case,ok", [
    ("same", True), ("rows_a_tenth_off", True), ("loss_b_off", False),
    ("a_leaf_left_as_it_was", False), ("rows_left_as_they_were", False),
    ("one_loss", False), ("not_finite", False)])
def test_the_gate_tells_a_wrong_update_apart(case, ok):
    """Each limit refuses alone: a loss 0.1% off; the rows left as they
    were (they read 1: as far from the reference as the reference moved);
    a small dense leaf left as it was, which reads 1 alone and hides in
    the dense vector (its share of the vector's change, here a hundredth);
    the rows' limit is wider than the vector's."""
    ref, leaves = gate()
    losses = {"loss_b_off": [2.0, 1.9 * 1.001], "one_loss": [2.0],
              "not_finite": [2.0, float("nan")]}.get(case, [2.0001, 1.9])
    got = dict(leaves)
    if case == "a_leaf_left_as_it_was":
        got["gate_b"] = np.float32(0.5 - 1e-3)        # before the update
    if case.startswith("rows"):
        off = 1e-3 if case == "rows_a_tenth_off" else 1e-2
        got["rows.mf"] = leaves["rows.mf"] - np.float32([[off, 0.0]])
    error = ref.update_error(got)
    want = {"a_leaf_left_as_it_was": ("gate_b", 1.0, 0.01),
            "rows_left_as_they_were": ("rows.mf", 1.0, 0.0),
            "rows_a_tenth_off": ("rows.mf", 0.1, 0.0)}.get(
                case, (None, 0.0, 0.0))
    for k, v in error["by_leaf"].items():
        assert v == pytest.approx(want[1] if k == want[0] else 0.0,
                                  rel=1e-3, abs=1e-9)
    assert error["dense"] == pytest.approx(want[2], rel=1e-3, abs=1e-9)
    assert error["rows"] == error["by_leaf"]["rows.mf"]
    out = ref.verdict(losses, error)
    assert out["ok"] is ok and out["update_error"] is error


def test_a_traced_run_refuses_the_bfloat16_control():
    """The traced run also puts the reference one precision down through
    the comparison in the program's place: refused, by the update of the
    parameters far more clearly than by the losses."""
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
    assert max(mine["by_leaf"].values()) < 1e-3 and mine["dense"] < 1e-3
    assert min(its["by_leaf"].values()) > 100 * max(mine["by_leaf"].values())
    assert its["dense"] > check["update_rtol"]["dense"] > 10 * mine["dense"]
    for name in ("step.mfu", "tower.device_share"):     # no device plane
        assert name not in result["metrics"]
    assert result["metrics"]["tower.padding_share"]["unit"] == "%"
