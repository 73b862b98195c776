"""The sparse step the cells measure (`mxu`) through the whole pass loop.

A lowering changes WIRE SHAPE only: `sparse_path="mxu"` must land on the
same losses, dense params and sparse table as the plain-XLA `reference`
step across optimizer rules, accessors and edge geometries, and on
itself bit for bit serial and prefetched, device cache on and off, and
across a crash and resume.  Also pins who may choose a lowering:
`sparse_path=` by name, and nothing else.
"""

import numpy as np
import pytest

from paddlebox_tpu import flags
from paddlebox_tpu.config import (AccessorConfig, DataFeedConfig,
                                  EmbeddingTableConfig, SlotConfig,
                                  SparseSGDConfig)
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.prefetch import PassPrefetcher
from paddlebox_tpu.data.slot_record import SlotRecordBlock
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.trainer.trainer import SparseTrainer
from paddlebox_tpu.utils.monitor import StatRegistry, stat_get

MF, CAP, B = 4, 3, 32
N_SLOTS = 4
N_DAYS, N_PASSES = 2, 3


@pytest.fixture(autouse=True)
def _clean_flags():
    prev = {k: flags.get_flags(k)
            for k in ("ps_device_cache", "ps_device_cache_rows")}
    StatRegistry.instance().reset()
    yield
    flags.set_flags(prev)


def _simple_cfg(n_slots=N_SLOTS):
    return DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=3)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=CAP)
           for i in range(n_slots)]))


def _simple_block(rng, n, n_keys=500, min_len=0, max_len=CAP,
                  empty_slot=None):
    """min_len=0 exercises empty slots; min_len=max_len=CAP the L=cap
    extreme; empty_slot=i forces slot i entirely empty in every record.
    Each slot draws from its own key range (offset 1000*(i+1)), as
    slot-hashed feasigns do: a key shared across slots records max(slot)
    under reference and its first occurrence's slot under mxu."""
    blk = SlotRecordBlock(n=n)
    for i in range(N_SLOTS):
        if i == empty_slot:
            lens = np.zeros(n, np.int64)
        else:
            lens = rng.integers(min_len, max_len + 1, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        keys = rng.integers(1, n_keys, size=int(off[-1])) + 1000 * (i + 1)
        blk.uint64_slots[f"s{i}"] = (keys.astype(np.uint64), off)
    blk.float_slots["label"] = (rng.integers(0, 2, n).astype(np.float32),
                                np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (
        rng.normal(0, 1, n * 3).astype(np.float32),
        np.arange(n + 1, dtype=np.int64) * 3)
    return blk


def _mk_table_cfg(optimizer="adagrad", accessor="ctr"):
    sgd = SparseSGDConfig(optimizer=optimizer, mf_create_thresholds=0.0)
    return EmbeddingTableConfig(
        embedding_dim=MF, shard_num=4, sgd=sgd,
        accessor=AccessorConfig(accessor_type=accessor))


def _model():
    return DeepFM(num_slots=N_SLOTS, emb_width=3 + MF, dense_dim=3,
                  hidden=(8,))


def _train_feed(sparse_path, blocks, table_cfg=None, passes=2):
    """Serial pass-resident loop (the feed every cell trains from)."""
    cfg = _simple_cfg()
    eng = BoxPSEngine(table_cfg or _mk_table_cfg(), seed=0)
    tr = SparseTrainer(eng, _model(), cfg, batch_size=B, seed=0,
                       sparse_path=sparse_path)
    losses = []
    for p in range(passes):
        ds = SlotDataset(cfg)
        ds._blocks = [blocks[p % len(blocks)]]
        eng.begin_feed_pass()
        for b in ds.get_blocks():
            eng.add_keys(b.all_keys())
        eng.end_feed_pass()
        eng.begin_pass()
        feed = tr.build_pass_feed(ds)
        losses.append(tr.train_pass(feed)["loss"])
        eng.end_pass()
    return losses, eng, tr


def _all_keys(blocks):
    return np.unique(np.concatenate(
        [v[0] for blk in blocks for v in blk.uint64_slots.values()]))


def _assert_same(a, b, keys, exact=True):
    losses1, eng1, tr1 = a
    losses2, eng2, tr2 = b
    close = (np.testing.assert_array_equal if exact
             else lambda x, y, err_msg="": np.testing.assert_allclose(
                 x, y, rtol=1e-4, atol=1e-5, err_msg=err_msg))
    close(np.asarray(losses1), np.asarray(losses2))
    s1, s2 = eng1.table.bulk_pull(keys), eng2.table.bulk_pull(keys)
    assert set(s1) == set(s2)
    for f in s1:
        close(np.asarray(s1[f]), np.asarray(s2[f]),
              err_msg=f"table field {f!r}")
    import jax
    for p1, p2 in zip(jax.tree_util.tree_leaves(tr1.params),
                      jax.tree_util.tree_leaves(tr2.params)):
        close(np.asarray(p1), np.asarray(p2))


# ---------------------------------------------------------------------------
# mxu against the plain-XLA reference.  Within a path the step is exactly
# deterministic (the bitwise tests below); ACROSS paths the reduction
# trees differ and mxu sums through a hi/lo bf16 split, so agreement is
# allclose, as in tests/test_mxu_path.py.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer", ["adagrad", "shared_adam"])
def test_mxu_step_matches_reference(optimizer):
    blocks = [_simple_block(np.random.default_rng(s), 96) for s in (0, 1)]
    tc = _mk_table_cfg(optimizer=optimizer)
    _assert_same(_train_feed("mxu", blocks, tc),
                 _train_feed("reference", blocks, tc),
                 _all_keys(blocks), exact=False)


@pytest.mark.parametrize("geometry", ["empty", "full"])
def test_mxu_empty_and_extreme_lengths(geometry):
    """Edge geometry: one slot empty in every record, or every slot at
    L == cap in every record — the trimmed plan handles both ends."""
    kw = (dict(empty_slot=2) if geometry == "empty"
          else dict(min_len=CAP, max_len=CAP))
    blocks = [_simple_block(np.random.default_rng(11), 64, **kw)]
    _assert_same(_train_feed("mxu", blocks, passes=1),
                 _train_feed("reference", blocks, passes=1),
                 _all_keys(blocks), exact=False)


def test_mxu_ctr_double():
    """ctr_double accessor: the per-pass show_acc/click_acc delta riders
    flow through apply_push and merge into the f64 host counters at
    end_pass."""
    blocks = [_simple_block(np.random.default_rng(3), 96)]
    keys = _all_keys(blocks)
    tc = _mk_table_cfg(accessor="ctr_double")
    mxu = _train_feed("mxu", blocks, tc)
    _assert_same(mxu, _train_feed("reference", blocks, tc), keys,
                 exact=False)
    show = np.asarray(mxu[1].table.bulk_pull(keys)["show"])
    assert show.dtype == np.float64 and show.max() > 0


# ---------------------------------------------------------------------------
# 2-day DeepFM e2e: serial == prefetched (packed on the worker thread).
# ---------------------------------------------------------------------------

def _mk_ds(cfg, day, p):
    ds = SlotDataset(cfg)
    ds._blocks = [_simple_block(np.random.default_rng(100 * day + 10 * p),
                                96, min_len=1)]
    return ds


def _run_days(prefetch, sparse_path):
    cfg = _simple_cfg()
    eng = BoxPSEngine(_mk_table_cfg(), seed=0)
    tr = SparseTrainer(eng, _model(), cfg, batch_size=B, seed=0,
                       sparse_path=sparse_path)
    losses = []
    if not prefetch:
        for day in range(N_DAYS):
            eng.set_date(f"2026080{day + 1}")
            for p in range(N_PASSES):
                ds = _mk_ds(cfg, day, p)
                eng.begin_feed_pass()
                for b in ds.get_blocks():
                    eng.add_keys(b.all_keys())
                eng.end_feed_pass()
                eng.begin_pass()
                feed = tr.build_pass_feed(ds)
                losses.append(tr.train_pass(feed)["loss"])
                eng.end_pass()
        return losses, eng, tr

    pre = PassPrefetcher(eng, tr)
    try:
        for day in range(N_DAYS):
            for p in range(N_PASSES):
                def load(day=day, p=p):
                    ds = _mk_ds(cfg, day, p)
                    for b in ds.get_blocks():
                        eng.add_keys(b.all_keys())
                    return ds
                pre.submit(load, tag=f"d{day}p{p}",
                           date=f"2026080{day + 1}")
        for _ in range(N_DAYS * N_PASSES):
            feed = pre.next_pass()
            losses.append(tr.train_pass(feed)["loss"])
            pre.end_pass()
    finally:
        pre.close()
    return losses, eng, tr


def _day_keys(cfg):
    parts = []
    for day in range(N_DAYS):
        for p in range(N_PASSES):
            for b in _mk_ds(cfg, day, p).get_blocks():
                parts.append(b.all_keys())
    return np.unique(np.concatenate(parts))


def test_mxu_two_day_e2e_serial_equals_prefetched():
    """The full 2-day x 3-pass DeepFM workload: host planes packed on the
    prefetch worker == packed inline, bit for bit."""
    _assert_same(_run_days(prefetch=False, sparse_path="mxu"),
                 _run_days(prefetch=True, sparse_path="mxu"),
                 _day_keys(_simple_cfg()), exact=True)


def test_mxu_device_cache_bit_identical():
    """PR 10 composition: DeviceRowCache fold-back sees the mxu step's
    updates — cache on == cache off over the full workload, with real
    hits."""
    flags.set_flags({"ps_device_cache": False})
    want = _run_days(prefetch=False, sparse_path="mxu")
    flags.set_flags({"ps_device_cache": True, "ps_device_cache_rows": 4096})
    got = _run_days(prefetch=True, sparse_path="mxu")
    _assert_same(want, got, _day_keys(_simple_cfg()), exact=True)
    assert stat_get("ps.cache.hits") > 0


# ---------------------------------------------------------------------------
# Crash/resume composition (PR 8 harness: seeded kill + auto-resume).
# ---------------------------------------------------------------------------

def _write_slot_file(path, rng, n):
    with open(path, "w") as f:
        for _ in range(n):
            parts = [f"1 {rng.integers(0, 2)}",
                     "3 " + " ".join(f"{rng.normal():.4f}"
                                     for _ in range(3))]
            for _s in range(N_SLOTS):
                k = rng.integers(1, CAP + 1)
                parts.append(f"{k} " + " ".join(
                    str(rng.integers(1, 500)) for _ in range(k)))
            f.write(" ".join(parts) + "\n")


def test_mxu_crash_resume_bit_identical(tmp_path):
    """Seeded kill at pass-1's end_pass: auto-resume rolls back and
    re-drives, and the re-built feeds (fresh plans) land on the
    uninterrupted run's state bit for bit."""
    from paddlebox_tpu import fleet
    from paddlebox_tpu.io.checkpoint import TrainCheckpoint
    from paddlebox_tpu.ps import faults

    cfg = _simple_cfg()
    files = []
    for p in range(3):
        path = str(tmp_path / f"p{p}.txt")
        _write_slot_file(path, np.random.default_rng(p), 48)
        files.append([path])

    def fresh():
        eng = BoxPSEngine(_mk_table_cfg(), seed=0)
        ds = fleet.BoxPSDataset(cfg, engine=eng, read_threads=1)
        tr = SparseTrainer(eng, _model(), cfg, batch_size=32, seed=0,
                           sparse_path="mxu")
        return eng, ds, tr

    eng1, ds1, tr1 = fresh()
    base = fleet.train_passes(tr1, ds1, files, date="20260801",
                              prefetch=False)

    flags.set_flags({"ps_fault_injection": True})
    eng2, ds2, tr2 = fresh()
    ck = TrainCheckpoint(str(tmp_path / "ckpt"))
    try:
        faults.install(faults.FaultPlan(seed=13).kill_at("end_pass",
                                                         at=(1,)))
        metrics = fleet.train_passes(tr2, ds2, files, date="20260801",
                                     prefetch=True, checkpoint=ck,
                                     resume=4)
    finally:
        faults.uninstall()
        flags.set_flags({"ps_fault_injection": False})

    np.testing.assert_array_equal([m["loss"] for m in base],
                                  [m["loss"] for m in metrics])
    keys = np.sort(np.concatenate([s.keys for s in eng1.table._shards]))
    s1, s2 = eng1.table.bulk_pull(keys), eng2.table.bulk_pull(keys)
    for f in s1:
        np.testing.assert_array_equal(np.asarray(s1[f]), np.asarray(s2[f]),
                                      err_msg=f"table field {f!r}")
    assert stat_get("ps.fault.lifecycle.kill") >= 1


# ---------------------------------------------------------------------------
# One selector: sparse_path= by name.  The lowering, the flag and the
# constructor bool that used to pick one are gone, and say so loudly.
# ---------------------------------------------------------------------------

def test_unknown_sparse_path_raises():
    blocks = [_simple_block(np.random.default_rng(0), 64)]
    with pytest.raises(ValueError, match="unknown sparse_path 'ragged'"):
        _train_feed("ragged", blocks, passes=1)


def test_fast_path_constructor_argument_is_gone():
    with pytest.raises(TypeError, match="fast_path"):
        SparseTrainer(BoxPSEngine(_mk_table_cfg(), seed=0), _model(),
                      _simple_cfg(), batch_size=B, fast_path=False)


def test_sparse_step_path_flag_is_gone():
    with pytest.raises(KeyError, match="sparse_step_path"):
        flags.set_flags({"sparse_step_path": "mxu"})
