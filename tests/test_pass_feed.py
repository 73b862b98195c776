"""Pass-resident feed: parity with the per-batch path, pack-rate floor,
and the perf-regression guards the bench geometry relies on."""

import time

import numpy as np
import pytest

from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.pass_feed import PlaneStore, pack_pass
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


# -- the pack reads the blocks where they lie: equal to the merged-copy pack --

def _oracle_pack(blocks, cfg, batch_size, label_slot="label", key_mapper=None,
                 prebatched=False, batch_counts=None, seq_key_slot=None,
                 head_keys=None):
    """The pack as it stood before PR 35, kept as the reference: one merged
    copy of the pass (``SlotRecordBlock.concat``), every plane padded
    through ``BatchPacker._pad_ragged`` / ``pad_sparse`` into fresh zeros."""
    from paddlebox_tpu.data import rank_offset as ro
    from paddlebox_tpu.data.batch_pack import BatchPacker
    packer = BatchPacker(cfg, batch_size, label_slot)
    blocks = list(blocks)
    m = SlotRecordBlock.concat(blocks)
    counts = ([int(c) for c in batch_counts] if batch_counts is not None
              else [b.n for b in blocks] if prebatched else None)
    real = base = None
    if counts is not None:
        n_batches = max(1, len(counts))
        pos = np.concatenate([np.zeros((0,), np.int64)] + [
            i * batch_size + np.arange(c) for i, c in enumerate(counts)])
        real = np.asarray(counts + [0] * (n_batches - len(counts)), np.int64)
        base = np.concatenate([[0], np.cumsum(real)[:-1]])
    else:
        n_batches, pos = max(1, -(-m.n // batch_size)), slice(0, m.n)
    nb, sparse = n_batches * batch_size, packer.sparse_slots
    out = {"indices": np.zeros((len(sparse), nb, packer.capacity), np.int32),
           "lengths": np.zeros((len(sparse), nb), np.int32),
           "dense": np.zeros((nb, packer.dense_dim), np.float32),
           "valid": np.zeros((nb,), bool)}
    multi = np.zeros((nb, len(packer.label_slots)), np.float32)
    out["valid"][pos] = True

    def padded(ragged, cap):
        return packer._pad_ragged(*ragged, cap)[0]
    col = 0
    for si, slot in enumerate(sparse if m.n else ()):
        v, o = m.uint64_slots[slot.name]
        v = key_mapper(v) if key_mapper is not None else v
        out["indices"][si, pos], out["lengths"][si, pos] = \
            packer.pad_sparse(slot, v, o)
    for slot in (packer.dense_slots if m.n else ()):
        out["dense"][pos, col:col + slot.dim] = padded(
            m.float_slots[slot.name], slot.dim)
        col += slot.dim
    for t, name in enumerate(packer.label_slots):
        src = m.float_slots if name in m.float_slots else m.uint64_slots
        if name in src:
            multi[pos, t] = padded(src[name], 1)[:, 0].astype(np.float32)
    out["labels"] = multi if multi.shape[1] > 1 else multi[:, 0]
    if cfg.uid_slot:
        out["uid"] = np.zeros((nb,), np.uint64)
        if m.n:
            out["uid"][pos] = padded(m.uint64_slots[cfg.uid_slot], 1)[:, 0]
    for slot in cfg.string_slots:
        out[slot.name] = np.zeros((nb, slot.capacity), np.int32)
        if m.n:
            out[slot.name][pos] = padded(m.aux_slots[slot.name],
                                         slot.capacity).astype(np.int32)
    if seq_key_slot:
        slot = next(s for s in sparse if s.name == seq_key_slot)
        out["seq_keys"] = np.zeros((nb, slot.capacity), np.int32)
        if m.n:
            out["seq_keys"][pos] = padded(m.uint64_slots[slot.name],
                                          slot.capacity).astype(np.int32)
    if cfg.rank_offset:
        out["rank_offset"] = ro.build_rank_offset_batched(
            m.search_ids, m.cmatch, m.rank, real, base, batch_size,
            cfg.max_rank)
    if cfg.ads_offset:
        out["ads_offset"] = ro.build_ads_offset_batched(
            m.search_ids, real, base, batch_size)
    if head_keys is not None:
        out["head_rows"] = np.ascontiguousarray(np.broadcast_to(
            key_mapper(np.asarray(head_keys, np.uint64)).astype(np.int32),
            (n_batches, len(head_keys))))
    return out, m.ins_ids


def _planes_of(h):
    """Every plane of a HostPassArrays by name, as the oracle names them."""
    out = {k: getattr(h, k) for k in ("indices", "lengths", "dense", "labels",
                                      "valid")}
    for k in ("uid", "rank_offset", "ads_offset", "head_rows"):
        if getattr(h, k) is not None:
            out[k] = getattr(h, k)
    out.update(h.aux or {})
    return out


def _assert_planes_equal(got, want):
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


PACK_B = 512        # batch size of the equality cases
PACK_SIZES = (5000, 0, 3000, 4411)      # 3 x 4,096 + 123 records: ranges of
# four threads cut inside blocks and across them, and the last batch is short


def _case_blocks(rng, caps, sizes=PACK_SIZES, keys_to=7, n_keys=3000,
                 with_ids=False, pv=False, aux=False, label2=False):
    """Blocks of ``sizes`` records, slot i holding 1..keys_to keys of
    1..n_keys (0..keys_to where a slot may be empty: keys_to > 1); the
    block of no records owns slots like any other."""
    blocks, rec = [], 0
    for n in sizes:
        blk = _make_block(rng, n, n_slots=len(caps), cap=1, n_keys=n_keys)
        for i in range(len(caps)):
            lens = (np.ones(n, np.int64) if keys_to == 1
                    else rng.integers(0, keys_to + 1, size=n))
            off = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
            blk.uint64_slots[f"s{i}"] = (rng.integers(
                1, n_keys, size=int(off[-1])).astype(np.uint64), off)
        if with_ids:
            blk.ins_ids = [f"ins{rec + r}" for r in range(n)]
        if pv:
            blk.search_ids = np.repeat(
                np.arange(rec, rec + n + 3, 3)[:-(-n // 3)], 3)[:n].astype(
                    np.uint64)
            blk.cmatch = rng.choice([222, 223, 224, 0], size=n).astype(np.int32)
            blk.rank = rng.integers(0, 5, size=n).astype(np.int32)
        if aux:
            lens = rng.integers(0, 4, size=n)
            off = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
            blk.aux_slots["user"] = (rng.integers(
                0, 90, size=int(off[-1])).astype(np.uint64), off)
        if label2:      # a second label, carried by a uint64 slot
            blk.uint64_slots["click2"] = (
                rng.integers(0, 2, size=n).astype(np.uint64),
                np.arange(n + 1, dtype=np.int64))
        blocks.append(blk)
        rec += n
    return blocks


def _cuts(rng, n, batch):
    """Per-batch record counts over n records, none above the batch."""
    counts = []
    while sum(counts) < n:
        counts.append(int(min(n - sum(counts), rng.integers(1, batch + 1))))
    return counts


def _pack_case(name, rng):
    """(blocks, feed config, keyword arguments of the pack) of one case."""
    caps, kw, block_kw, cfg_kw, extra = (3, 1, 2, 5), {}, {}, {}, []
    if name == "one_key":
        caps, block_kw = (1, 1, 1, 1, 1), {"keys_to": 1}
    elif name == "one_key_stored_wide":       # one slot makes the plane wide
        caps, block_kw = (1, 4, 1), {"keys_to": 1}
    elif name == "one_block":       # a shuffled dataset: ranges cut inside
        block_kw = {"sizes": (9000,)}
    elif name == "empty_pass":
        block_kw = {"sizes": ()}
    elif name == "no_records":
        block_kw = {"sizes": (0, 0)}
    elif name == "prebatched":
        block_kw = {"sizes": (PACK_B, 17, 0, 300, PACK_B, 1) * 4}
        kw = {"prebatched": True}
    elif name == "rank_ads_offset":
        cfg_kw, block_kw = {"rank_offset": True, "ads_offset": True}, \
            {"pv": True, "with_ids": True}
    elif name == "uid_slot":
        cfg_kw = {"uid_slot": "s1"}
    elif name == "string_slot":
        extra, block_kw = [SlotConfig("user", dtype="string", capacity=2)], \
            {"aux": True}
    elif name == "seq_key_slot":
        kw = {"seq_key_slot": "s3"}
    elif name == "head_keys":
        kw = {"head_keys": np.array([5, 9_999_999, 17, 1], np.uint64)}
    elif name == "multi_label":
        extra, block_kw = [SlotConfig("click2", slot_id=7)], {"label2": True}
        kw = {"label_slot": ["label", "click2", "absent"]}
    else:
        assert name in ("ragged_clipped", "one_block", "batch_counts"), name
    blocks = _case_blocks(rng, caps, **block_kw)
    if name in ("batch_counts", "rank_ads_offset"):
        kw["batch_counts"] = _cuts(rng, sum(b.n for b in blocks), PACK_B)
    cfg = DataFeedConfig(slots=tuple(
        list(_feed_config(n_slots=len(caps), cap=caps).slots) + extra),
        **cfg_kw)
    return blocks, cfg, kw


PACK_CASES = ["one_key", "one_key_stored_wide", "ragged_clipped",
              "one_block", "empty_pass", "no_records", "prebatched", "batch_counts",
              "rank_ads_offset", "uid_slot", "string_slot", "seq_key_slot",
              "head_keys", "multi_label"]


@pytest.mark.parametrize("case,threads,mapped", [
    pytest.param(c, t, m, id=f"{c}-{t}-{'mapper' if m else 'raw_keys'}")
    for c in PACK_CASES for t in (1, 4) for m in (True, False)
    if m or c != "head_keys"])      # head_keys need a key mapper
def test_pack_equals_the_merged_copy_pack(case, threads, mapped):
    """Every plane the pack writes from the blocks where they lie is the
    plane the merged-copy pack wrote, bit for bit, at one pack thread and
    at four, with the pass's key mapper and without; the clipped keys are
    counted alike, and a group of records all of one length lands strided."""
    from paddlebox_tpu.utils.monitor import stat_get
    blocks, cfg, kw = _pack_case(case, np.random.default_rng(35))
    n = sum(b.n for b in blocks)
    mapper = None
    if mapped:      # rows of every second key; the others read row 0
        mapper = PassKeyMapper(np.arange(2, 3000, 2, dtype=np.uint64))
    c0 = stat_get("data.pack.clipped_keys")
    want, want_ids = _oracle_pack(blocks, cfg, PACK_B, key_mapper=mapper, **kw)
    c1 = stat_get("data.pack.clipped_keys")
    g0 = [stat_get("data.pack.groups_strided"),
          stat_get("data.pack.groups_scattered")]
    got = pack_pass(blocks, cfg, PACK_B, key_mapper=mapper,
                    pack_threads=threads, **kw)
    _assert_planes_equal(_planes_of(got), want)
    assert got.ins_ids == want_ids and got.num_real == n
    assert got.n_batches * PACK_B == len(got.valid)
    assert stat_get("data.pack.clipped_keys") - c1 == c1 - c0
    if case == "ragged_clipped":
        assert c1 - c0 > 0
    strided = stat_get("data.pack.groups_strided") - g0[0]
    scattered = stat_get("data.pack.groups_scattered") - g0[1]
    if n and case.startswith("one_key"):
        assert scattered == 0 and strided > 0
    elif n:
        assert scattered > 0 and strided > 0      # dense and label: strided
    else:
        assert strided == scattered == 0


def test_pack_without_mapper_refuses_keys_beyond_int32():
    rng = np.random.default_rng(3)
    blocks = _case_blocks(rng, (2, 2), sizes=(300, 200))
    vals, offs = blocks[1].uint64_slots["s1"]
    vals[-1] = np.uint64(1) << np.uint64(40)
    cfg = _feed_config(n_slots=2, cap=(2, 2))
    with pytest.raises(ValueError, match="keys exceed int32"):
        pack_pass(blocks, cfg, 64)
    with pytest.raises(ValueError, match="seq_keys: keys of slot 's1'"):
        pack_pass(blocks, cfg, 64, key_mapper=lambda k: k.astype(np.int32),
                  seq_key_slot="s1")


# -- the planes are kept from pass to pass --------------------------------

class ScribblingPlaneStore(PlaneStore):
    """Every buffer that comes back is overwritten before it can be handed
    out again: a reader that still held a plane, or a pack that left a
    byte of one unwritten, shows as 0xFF."""

    def give_back(self, buffers):
        for buf in buffers or ():
            buf[:] = 0xFF
        super().give_back(buffers)


def _plane_stats():
    import json
    from paddlebox_tpu.utils import obs_server
    statz = json.loads(obs_server.render_statz(prefix="data.pack"))
    return (statz.get("data.pack.plane_bytes_reused", 0.0),
            statz.get("data.pack.plane_bytes_fresh", 0.0))


def _drive_pass(eng, tr, ds, blocks, keep_host=False):
    """One pass through the engine's lifecycle and the trainer's two
    halves of the feed build; answers (host planes as packed, feed), the
    planes with ``took``: the (reused, fresh) bytes their pack counted."""
    ds._blocks = blocks
    eng.begin_feed_pass()
    for b in blocks:
        eng.add_keys(b.all_keys())
    eng.end_feed_pass()
    eng.begin_pass()
    before = _plane_stats()
    arrays = tr.pack_pass_host(ds)
    arrays.took = tuple(np.subtract(_plane_stats(), before))
    want = pack_pass(blocks, tr.packer.config, tr.batch_size,
                     key_mapper=eng.mapper)
    _assert_planes_equal(_planes_of(arrays), _planes_of(want))
    feed = tr.finish_pass_feed(arrays, keep_host=keep_host)
    return arrays, feed


def test_kept_planes_pack_what_fresh_planes_pack():
    """Two seeded passes of other content in turn, four times, through one
    trainer whose store scribbles over every plane handed back: each pack
    equals a pack into fresh planes, the first allocates and every later
    one is answered from the kept set (one set, no prefetcher)."""
    rng = np.random.default_rng(5)
    passes = [[_make_block(rng, 100), _make_block(rng, 50)],
              [_make_block(rng, 30), _make_block(rng, 111)]]
    ds, eng, tr = _build(passes[0], "fast", batch_size=64)
    eng.end_pass()
    tr._plane_store = ScribblingPlaneStore()
    losses = []
    for k in range(8):
        arrays, feed = _drive_pass(eng, tr, ds, passes[k % 2])
        reused, fresh = arrays.took
        assert (reused > 0, fresh > 0) == (k > 0, k == 0)
        assert feed.storage is arrays.storage and tr._plane_store.free_bytes == 0
        losses.append(tr.train_pass(feed)["loss"])
        assert feed.storage is None and tr._plane_store.free_bytes > 0
        assert (arrays.indices.view(np.uint8) == 0xFF).all()    # handed back
        eng.end_pass()
    assert np.isfinite(losses).all()


def test_a_small_pass_after_a_large_one_pads_anew():
    """A kept plane holds the last pass's rows: after a pass of three full
    batches, one of a batch and a half reads padding beyond its records
    (``valid`` false, rows and lengths zero), as a fresh pack does."""
    rng = np.random.default_rng(6)
    cfg = _feed_config()
    store = ScribblingPlaneStore()
    large = pack_pass([_make_block(rng, 192)], cfg, 64, planes=store)
    assert large.valid.all() and (large.lengths > 0).all()
    store.give_back(large.storage)
    blocks = [_make_block(rng, 96)]
    small = pack_pass(blocks, cfg, 64, planes=store)
    _assert_planes_equal(_planes_of(small),
                         _planes_of(pack_pass(blocks, cfg, 64)))
    assert small.n_batches == 2 and not small.valid[96:].any()
    assert not small.indices[:, 96:].any() and not small.lengths[:, 96:].any()
    assert not small.dense[96:].any() and not small.labels[96:].any()
    # the planes are views of the large pass's buffers, not new ones
    assert {b.ctypes.data for b in small.storage} <= \
        {b.ctypes.data for b in large.storage}
    # cut on page-view counts the padding lies inside every short batch
    store.give_back(small.storage)
    cut = pack_pass(blocks, cfg, 64, batch_counts=[40, 0, 56], planes=store)
    _assert_planes_equal(
        _planes_of(cut),
        _planes_of(pack_pass(blocks, cfg, 64, batch_counts=[40, 0, 56])))


@pytest.mark.parametrize("why", ["keep_host", "dump_path", "uid_slot"])
def test_a_feed_that_reads_host_planes_owns_them(why, tmp_path):
    """``keep_host`` (and ``dump_path``, and a ``uid_slot``'s host labels)
    keep reading the host planes for the life of the feed: they are never
    handed back, so a later pack cannot write them."""
    from paddlebox_tpu.config import TrainerConfig
    rng = np.random.default_rng(8)
    passes = [[_make_block(rng, 100)], [_make_block(rng, 90)]]
    cfg = _feed_config()
    if why == "uid_slot":
        cfg = DataFeedConfig(slots=cfg.slots, uid_slot="s0")
    ds = SlotDataset(cfg)
    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=MF, sgd=SparseSGDConfig(mf_create_thresholds=0.0)))
    tr = SparseTrainer(
        eng, DeepFM(num_slots=N_SLOTS, emb_width=3 + MF, dense_dim=DENSE_DIM,
                    hidden=(16,)), cfg, batch_size=64, seed=0,
        sparse_path="fast", trainer_config=TrainerConfig(
            dump_path=str(tmp_path) if why == "dump_path" else ""))
    tr._plane_store = ScribblingPlaneStore()
    arrays, feed = _drive_pass(eng, tr, ds, passes[0],
                               keep_host=why == "keep_host")
    kept = {k: v.copy() for k, v in _planes_of(arrays).items()}
    assert feed.storage is None
    tr.train_pass(feed)
    eng.end_pass()
    assert tr._plane_store.free_bytes == 0
    other, feed2 = _drive_pass(eng, tr, ds, passes[1])
    assert not any(np.shares_memory(a, b) for a in _planes_of(arrays).values()
                   for b in _planes_of(other).values())
    _assert_planes_equal(_planes_of(arrays), kept)
    if why == "uid_slot":
        np.testing.assert_array_equal(feed.host_labels, kept["labels"])
        np.testing.assert_array_equal(feed.uid, kept["uid"])
    else:
        assert feed.host is arrays
    tr.train_pass(feed)         # the first feed still trains, on its planes
    eng.end_pass()


def test_prefetched_passes_alternate_two_kept_sets():
    """Under the prefetcher a pass is packed while the last one trains:
    the first pass allocates its planes (and the second, packed beside
    it), and from the third every plane byte is answered from a set
    handed back before (the share ``/statz`` shows)."""
    from paddlebox_tpu.data.prefetch import PassPrefetcher
    rng = np.random.default_rng(9)
    passes = [[_make_block(rng, 150)], [_make_block(rng, 140)]]
    ds, eng, tr = _build(passes[0], "fast", batch_size=64)
    eng.end_pass()
    tr._plane_store = ScribblingPlaneStore()
    seen = []

    def load(k):
        seen.append(_plane_stats())     # on the worker, ahead of pass k's pack
        ds._blocks = passes[k % 2]
        for b in ds.get_blocks():
            eng.add_keys(b.all_keys())
        return ds

    with PassPrefetcher(eng, tr) as pre:
        for k in range(5):
            pre.submit(lambda k=k: load(k))
        for k in range(5):
            feed = pre.next_pass()
            want, _ = _oracle_pack(passes[k % 2], tr.packer.config, 64,
                                   key_mapper=eng.mapper)   # counts no plane
            np.testing.assert_array_equal(
                np.asarray(feed.data["indices"]),
                want["indices"].reshape(N_SLOTS, -1, 64, CAP).transpose(
                    1, 0, 3, 2))
            tr.train_pass(feed)
            pre.end_pass()
    seen.append(_plane_stats())
    shares = [(r1 - r0) / ((r1 - r0) + (f1 - f0))
              for (r0, f0), (r1, f1) in zip(seen, seen[1:])]
    # the second pass is packed while the first compiles its step and
    # trains; had the first handed its set back by then, the second would
    # find it (as where the build is slower than the training)
    assert shares[0] == 0.0 and 0.0 <= shares[1] <= 1.0
    assert shares[2:] == [1.0, 1.0, 1.0]
