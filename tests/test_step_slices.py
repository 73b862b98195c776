"""How the packed step takes batch ``i`` out of the pass-resident planes.

``trainer._build_packed_step`` cuts batch ``i``'s slices of ``data`` and of
``plans`` at the top of the step, behind one ``optimization_barrier``: the
pull, which reads the plan slices, then cannot start before the dense
slice exists, so a whole-pass plane the compiler keeps in fast memory for
that slice is handed back before the pull's crossing runs.  The barrier
reorders no arithmetic: two passes train to the same bits as the step
that sliced each plane beside its reader (pinned below from that step).
"""

import functools
import hashlib
import re

import jax
import numpy as np
import pytest

from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.slot_record import SlotRecordBlock
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.trainer.trainer import SparseTrainer

MF, B, BATCHES, PASSES = 4, 32, 3, 2
# DeepFM's shape (one key a slot: pooling is a no-op) and a sequence
# shape (two capacity groups, pooled)
GEOMETRIES = {"deepfm": (1, 1, 1, 1), "sequence": (1, 3, 1, 3)}

# Two passes through the mxu path (pass plans) and the plain-XLA
# reference path (no plans), taken from the step that sliced each plane
# beside its reader: the per-step losses as float32 bits, and sha256 of
# the AUC state's arrays and of the rows written back to the host table.
PINNED = {
    ("deepfm", "mxu"): {
        "losses": [1059928776, 1061236207, 1059743646,
                   1060915338, 1059971980, 1060384894],
        "auc": "85cf7aee94cbe459996a630e3a81426f"
                "bfae801252b9bc44afea34ced8d91d3d",
        "rows": "b3d0319491a04845a3581e713f5af019"
                "d2e2ba12dba8d988dae4da012e7ca75c"},
    ("deepfm", "reference"): {
        "losses": [1059928776, 1061236207, 1059743646,
                   1060915338, 1059971980, 1060384894],
        "auc": "85cf7aee94cbe459996a630e3a81426f"
                "bfae801252b9bc44afea34ced8d91d3d",
        "rows": "cbbe38a9145b693eac385877befb44d1"
                "186b1ad08b6dfdb5c53a17c6e8ce0deb"},
    ("sequence", "mxu"): {
        "losses": [1060851610, 1060852754, 1060429364,
                   1060266272, 1060266132, 1061004928],
        "auc": "baea442f4aa061215dc4de7d32f86825"
                "0d0c78da8c88387e8d0588b408615493",
        "rows": "cace6112e27ea1b4e6137fea55780029"
                "1451c1ffa8fea31da6afe523e9851006"},
    ("sequence", "reference"): {
        "losses": [1060851610, 1060852754, 1060429364,
                   1060266272, 1060266132, 1061004928],
        "auc": "baea442f4aa061215dc4de7d32f86825"
                "0d0c78da8c88387e8d0588b408615493",
        "rows": "2540068d0df15ed449b0cce0106a8459"
                "81bb5e85581a66aec6aafc6847b5c9b2"},
}


def feed_config(caps):
    return DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=3)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=c)
           for i, c in enumerate(caps)]))


def block(caps, n, seed):
    rng = np.random.default_rng(seed)
    blk = SlotRecordBlock(n=n)
    for i, c in enumerate(caps):
        lens = rng.integers(1 if c == 1 else 0, c + 1, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        keys = rng.integers(1, 300, size=int(off[-1])) + 1000 * (i + 1)
        blk.uint64_slots[f"s{i}"] = (keys.astype(np.uint64), off)
    blk.float_slots["label"] = (rng.integers(0, 2, n).astype(np.float32),
                                np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (rng.normal(0, 1, n * 3).astype(np.float32),
                                 np.arange(n + 1, dtype=np.int64) * 3)
    return blk


def make_trainer(caps, path):
    engine = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=MF, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)), seed=0)
    model = DeepFM(num_slots=len(caps), emb_width=3 + MF, dense_dim=3,
                   hidden=(8,))
    return SparseTrainer(engine, model, feed_config(caps), batch_size=B,
                         seed=0, sparse_path=path)


@functools.lru_cache(maxsize=None)
def train(geometry, path):
    """PASSES passes of BATCHES batches through the pass-resident feed:
    (trainer, the last pass's feed, per-step losses, the keys seen)."""
    caps = GEOMETRIES[geometry]
    trainer = make_trainer(caps, path)
    engine = trainer.engine
    losses, keys, feed = [], [], None
    for p in range(PASSES):
        ds = SlotDataset(feed_config(caps))
        ds._blocks = [block(caps, BATCHES * B, seed=p)]
        keys.append(ds._blocks[0].all_keys())
        engine.begin_feed_pass()
        engine.add_keys(keys[-1])
        engine.end_feed_pass()
        engine.begin_pass()
        feed = trainer.build_pass_feed(ds)
        assert trainer._resolve_path() == path
        assert (feed.plans is not None) == (path == "mxu")
        losses += trainer.train_pass(feed)["losses"]
        engine.end_pass()
    return trainer, feed, losses, np.unique(np.concatenate(keys))


def digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("path", ["mxu", "reference"])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_two_passes_train_to_the_pinned_bits(geometry, path):
    trainer, _, losses, keys = train(geometry, path)
    rows = trainer.engine.table.bulk_pull(keys)
    assert {
        "losses": [int(np.float32(x).view(np.uint32)) for x in losses],
        "auc": digest(jax.tree.leaves(trainer.auc_state)),
        "rows": digest(rows[f] for f in sorted(rows)),
    } == PINNED[(geometry, path)]


# -- the StableHLO of the step ------------------------------------------------
# what the step's entry may do before the barrier: batch i's index (i, or
# i + n where i < 0) and one dynamic_slice + reshape a plane
BEFORE_BARRIER = {"constant", "compare", "add", "select", "dynamic_slice",
                  "reshape"}
DEFINES = re.compile(r"^\s*(%[\w#:]+?)(?::\d+)? = (?:\"?stablehlo\.(\w+))")


def main_function(text):
    """(argument numbers, the lines of its body) of the step's entry."""
    start = text.index("func.func public @main")
    end = text.find("\n  func.func", start + 1)
    header, *body = text[start:end if end > 0 else None].splitlines()
    return [int(n) for n in re.findall(r"%arg(\d+):", header)], body


@pytest.mark.parametrize("path", ["mxu", "reference"])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_every_plane_is_cut_first_behind_one_barrier(geometry, path):
    """Every leaf of ``data`` and ``plans`` (the step's last arguments) is
    sliced and enters the one barrier, and the step does nothing else
    before it: no gather, no kernel, no call."""
    trainer, feed, _, _ = train(geometry, path)
    args, body = main_function(trainer.step_lowered().as_text())
    barriers = [k for k, line in enumerate(body)
                if "optimization_barrier" in line]
    assert len(barriers) == 1, barriers
    top = body[barriers[0]]
    ops = {}                                # value -> (op, operands)
    for line in body[:barriers[0]]:
        m = DEFINES.match(line)
        assert m and m.group(2) in BEFORE_BARRIER, line
        rhs = line.split(" = ", 1)[1].split(" : ")[0]
        ops[m.group(1)] = (m.group(2), re.findall(r"%[\w#]+", rhs))
    planes = jax.tree.leaves((feed.data, feed.plans or {}))
    operands = re.findall(r"%[\w#]+", top.split("optimization_barrier")[1]
                          .split(" : ")[0])
    sliced = []
    for v in operands:
        op, (src,) = ops[v]
        assert op == "reshape", (v, op)
        op, (arg, *_) = ops[src]
        assert op == "dynamic_slice", (src, op)
        sliced.append(arg)
    assert sliced == [f"%arg{n}" for n in args[-len(planes):]]
