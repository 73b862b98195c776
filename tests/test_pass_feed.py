"""Pass-resident feed: parity with the per-batch path, pack-rate floor,
and the perf-regression guards the bench geometry relies on."""

import time

import numpy as np
import pytest

from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.pass_feed import pack_pass
from paddlebox_tpu.data.slot_record import SlotRecordBlock
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.ps.embedding import PassKeyMapper
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.trainer.trainer import SparseTrainer

N_SLOTS, DENSE_DIM, MF, CAP = 4, 3, 4, 3


def _feed_config(n_slots=N_SLOTS, cap=CAP, dense_dim=DENSE_DIM):
    """cap: one capacity for every slot, or one a slot."""
    caps = [cap] * n_slots if isinstance(cap, int) else list(cap)
    return DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=dense_dim)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=caps[i])
           for i in range(n_slots)]))


def _make_block(rng, n, n_slots=N_SLOTS, cap=CAP, dense_dim=DENSE_DIM,
                n_keys=500):
    blk = SlotRecordBlock(n=n)
    for i in range(n_slots):
        lens = rng.integers(1, cap + 1, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        blk.uint64_slots[f"s{i}"] = (
            rng.integers(1, n_keys, size=int(off[-1])).astype(np.uint64), off)
    blk.float_slots["label"] = (
        rng.integers(0, 2, size=n).astype(np.float32),
        np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (
        rng.normal(0, 1, size=n * dense_dim).astype(np.float32),
        np.arange(n + 1, dtype=np.int64) * dense_dim)
    return blk


def _build(blocks, sparse_path="auto", batch_size=64, cap=CAP):
    cfg = _feed_config(cap=cap)
    ds = SlotDataset(cfg)
    ds._blocks = blocks
    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=MF, sgd=SparseSGDConfig(mf_create_thresholds=0.0)))
    eng.begin_feed_pass()
    for b in ds.get_blocks():
        eng.add_keys(b.all_keys())
    eng.end_feed_pass()
    eng.begin_pass()
    model = DeepFM(num_slots=N_SLOTS, emb_width=3 + MF, dense_dim=DENSE_DIM,
                   hidden=(16,))
    tr = SparseTrainer(eng, model, cfg, batch_size=batch_size, seed=0,
                       sparse_path=sparse_path)
    return ds, eng, tr


@pytest.mark.parametrize("sparse_path", ["mxu", "fast", "reference"])
def test_packed_matches_per_batch(sparse_path):
    rng = np.random.default_rng(0)
    blocks = [_make_block(rng, 150)]

    ds1, eng1, tr1 = _build(blocks, sparse_path)
    stats1 = tr1.train_pass(ds1)

    ds2, eng2, tr2 = _build(blocks, sparse_path)
    feed = tr2.build_pass_feed(ds2)
    if sparse_path == "mxu":
        assert feed.plans is not None, "mxu feed must precompute plans"
    stats2 = tr2.train_pass(feed)

    assert stats1["batches"] == stats2["batches"] == 3
    assert np.isclose(stats1["loss"], stats2["loss"], atol=1e-6)
    assert np.isclose(stats1["auc"], stats2["auc"], atol=1e-6)
    for k in eng1.ws:
        np.testing.assert_allclose(np.asarray(eng1.ws[k]),
                                   np.asarray(eng2.ws[k]), atol=1e-5,
                                   err_msg=k)


def test_packed_feed_is_reusable_across_paths():
    """The feed carries data only; a second pass over the same feed trains
    further (the loop must not donate/consume the feed arrays)."""
    rng = np.random.default_rng(1)
    ds, eng, tr = _build([_make_block(rng, 100)], "mxu")
    feed = tr.build_pass_feed(ds)
    s1 = tr.train_pass(feed)
    s2 = tr.train_pass(feed)
    assert s1["batches"] == s2["batches"] == 2
    assert s2["loss"] < s1["loss"] + 1e-6  # training continued


def test_pack_rate_floor():
    """Guard: whole-pass packing must stay ~2 orders faster than the
    per-batch numpy path it replaced (BENCH_r03's 27k ex/s bottleneck).
    Floor is set ~3x under the measured single-CPU rate to stay unflaky."""
    rng = np.random.default_rng(2)
    n = 50_000
    cfg = _feed_config(n_slots=8)
    blk = _make_block(rng, n, n_slots=8, n_keys=200_000)
    keys = np.unique(np.concatenate(
        [v[0] for v in blk.uint64_slots.values()]))
    mapper = PassKeyMapper(keys[keys != 0])
    t0 = time.perf_counter()
    arrays = pack_pass([blk], cfg, 4096, "label", key_mapper=mapper)
    rate = n / (time.perf_counter() - t0)
    assert arrays.indices.shape[0] == 8 and arrays.indices.shape[2] == 3
    assert arrays.indices.shape[1] % 4096 == 0  # padded to whole batches
    assert rate > 100_000, f"pass pack regressed to {rate:,.0f} ex/s"


def test_native_mapper_matches_searchsorted():
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(1, 10**9, size=300_000).astype(np.uint64))
    m = PassKeyMapper(keys)
    q = rng.integers(0, 10**9, size=200_000).astype(np.uint64)
    got = m(q)  # above native threshold
    pos = np.searchsorted(keys, q)
    pos_c = np.minimum(pos, len(keys) - 1)
    ref = np.where(keys[pos_c] == q, pos_c + 1, 0).astype(np.int32)
    assert np.array_equal(got, ref)


def test_auto_resolves_to_mxu_at_bench_geometry():
    """A silent fallback off the mxu path at the bench geometry would pass
    every numeric test and quietly halve throughput — pin it here."""
    rng = np.random.default_rng(4)
    ds, eng, tr = _build([_make_block(rng, 64)], "auto")
    assert tr._resolve_path() == "mxu"
    tr.sparse_path = "reference"
    assert tr._resolve_path() == "reference"


def test_feed_plans_are_trimmed_when_lengths_vary():
    """build_pass_feed must engage occurrence trimming whenever avg_len <
    capacity (sorted_spmm.trimmed_dims): a regression to untrimmed plans
    silently re-adds ~1.5x kernel + push-crossing work at bench geometry."""
    from paddlebox_tpu.ops import sorted_spmm as sp
    from paddlebox_tpu.ps import mxu_path
    rng = np.random.default_rng(9)
    # big enough that the 1/8th-width trim buckets resolve below full
    # (tiny geometries round back up to untrimmed — also asserted here)
    ds, eng, tr = _build([_make_block(rng, 2048)], "mxu", batch_size=2048)
    feed = tr.build_pass_feed(ds)
    n, s, l, b = feed.data["indices"].shape
    dims = mxu_path.make_dims(s * l * b, eng.ws["show"].shape[0])
    n_chunks_eff = feed.plans["rows2d"].shape[1]
    assert n_chunks_eff < dims.n_chunks, (n_chunks_eff, dims.n_chunks)
    # and every real occurrence survives the trim
    per_batch = np.asarray(feed.data["lengths"]).sum(axis=(1, 2))
    assert n_chunks_eff * dims.chunk >= per_batch.max()


def test_sort_crossing_trains_identically():
    """FLAGS_mxu_crossing=sort through the REAL packed train_pass must
    reproduce the take lowering's loss/AUC exactly (the crossings are
    pure permutations — any divergence is a plan/crossing bug)."""
    from paddlebox_tpu import flags

    def run():
        rng = np.random.default_rng(11)
        ds, eng, tr = _build([_make_block(rng, 256)], "mxu")
        feed = tr.build_pass_feed(ds)
        return tr.train_pass(feed)

    old = flags.get_flags("mxu_crossing")
    try:
        flags.set_flags({"mxu_crossing": "take"})
        a = run()
        flags.set_flags({"mxu_crossing": "sort"})
        b = run()
    finally:
        flags.set_flags({"mxu_crossing": old})
    assert a["batches"] == b["batches"]
    np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a["auc"], b["auc"], rtol=1e-5, atol=1e-6)


def test_spmm_worklist_bound_driver_geometry():
    """n_work is the static worklist bound: n_chunks + n_tiles, independent
    of the key distribution.  At the driver geometry it must stay ~3.5k —
    a regression here multiplies kernel grid overhead directly."""
    from paddlebox_tpu.ops import sorted_spmm as sp
    dims = sp.spmm_dims(26 * 3 * 16384, 2_000_000)
    assert dims.n_work == dims.n_chunks + dims.n_tiles
    assert dims.n_work <= 3_600, dims


def test_save_state_none_on_deleted_buffers():
    """Failed donated step: _save_state must park dead state groups at None
    (clear lifecycle error later) instead of keeping deleted buffers."""
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    ds, eng, tr = _build([_make_block(rng, 64)], "mxu")
    ws = eng.ws
    live_params = tr.params
    dead = jnp.ones((4,))
    dead.delete()
    tr._save_state({"x": dead}, live_params, tr.opt_state, tr.auc_state)
    assert eng.ws is None
    assert tr.params is live_params


def test_first_occ_slot_exact_under_multi_slot_key():
    """A key occurring under two slots must record the slot of its first
    occurrence (canonical order) — not a rounded average of slot ids."""
    import jax.numpy as jnp
    from paddlebox_tpu.ops import sorted_spmm as sp
    rows = jnp.asarray(np.array([5, 7, 5, 9], np.int32))
    dims = sp.spmm_dims(4, 16, chunk=8, tile=16)
    plan = sp.build_plan(rows, dims)
    first_occ = np.asarray(plan[7])
    srt = np.asarray(plan[0]).reshape(-1)
    # duplicates of row 5: only the first sorted position is marked
    dup_pos = np.nonzero(srt == 5)[0]
    assert first_occ[dup_pos[0]] == 1.0 and first_occ[dup_pos[1]] == 0.0


# -- a slot's declared capacity bounds what is packed, pulled and pushed ----

MIXED_CAPS = (1, 3, 2, 3)


@pytest.mark.parametrize("packer", ["batch", "pass"])
def test_record_is_clipped_at_its_slots_capacity(packer):
    """Both packers clip a record's keys at ITS slot's capacity (not the
    widest slot's): ``lengths`` says so, the positions beyond hold
    padding, and ``data.pack.clipped_keys`` counts what was left out."""
    from paddlebox_tpu.data.batch_pack import BatchPacker
    from paddlebox_tpu.utils.monitor import stat_get
    rng = np.random.default_rng(21)
    n, b = 40, 64
    blk = _make_block(rng, n)           # every slot holds 1..CAP keys
    cfg = _feed_config(cap=MIXED_CAPS)
    before = stat_get("data.pack.clipped_keys")
    if packer == "batch":
        out = BatchPacker(cfg, b).pack(
            blk, key_mapper=lambda ks: ks.astype(np.int64))
        indices, lengths = out.indices, out.lengths     # [S, B, L]
    else:
        out = pack_pass([blk], cfg, b)
        indices, lengths = out.indices, out.lengths     # [S, N*B, L]
    assert indices.shape == (N_SLOTS, b, CAP)
    clipped = 0
    for si, cap in enumerate(MIXED_CAPS):
        vals, offs = blk.uint64_slots[f"s{si}"]
        lens = np.diff(offs)
        np.testing.assert_array_equal(lengths[si, :n], np.minimum(lens, cap))
        assert (indices[si, :, cap:] == 0).all()
        for r in range(n):
            k = min(int(lens[r]), cap)
            np.testing.assert_array_equal(indices[si, r, :k],
                                          vals[offs[r]:offs[r] + k])
            assert (indices[si, r, k:] == 0).all()
        clipped += int(np.maximum(lens - cap, 0).sum())
    assert clipped > 0
    assert stat_get("data.pack.clipped_keys") - before == clipped


@pytest.mark.parametrize("resident", [True, False])
def test_key_beyond_its_slots_capacity_is_neither_pulled_nor_pushed(resident):
    """A key beyond its slot's declared capacity trains nothing: its row
    receives no push (show stays as pulled), on the pass-resident feed and
    on the per-batch path; and the step's gauges say how many rows the
    pull crossing emits against the canonical rectangle."""
    from paddlebox_tpu.utils.monitor import stat_get
    rng = np.random.default_rng(22)
    n, b = 128, 64
    blk = _make_block(rng, n)
    # slot s0 declares one key; every record carries a second, unique one
    vals, offs = blk.uint64_slots["s0"]
    first = vals[offs[:-1]]
    extra = (10_000 + np.arange(n)).astype(np.uint64)
    blk.uint64_slots["s0"] = (
        np.stack([first, extra], axis=1).reshape(-1),
        2 * np.arange(n + 1, dtype=np.int64))
    ds, eng, tr = _build([blk], "mxu", batch_size=b, cap=(1, 3, 1, 3))
    rows_extra, rows_first = eng.mapper(extra), eng.mapper(first)
    assert (rows_extra > 0).all()       # in the working set, never trained
    show0 = np.asarray(eng.ws["show"]).copy()
    tr.train_pass(tr.build_pass_feed(ds) if resident else ds)
    show1 = np.asarray(eng.ws["show"])
    np.testing.assert_array_equal(show1[rows_extra], show0[rows_extra])
    assert (show1[rows_first] > show0[rows_first]).all()
    assert stat_get("ps.mxu.pull_cross_rows") == (1 + 3 + 1 + 3) * b
    assert stat_get("ps.mxu.pull_cross_rows_canonical") == N_SLOTS * CAP * b
