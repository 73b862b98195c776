"""Pipelined pass-feed engine (ISSUE 8): parallel pack bit-identity over
the full plane surface, batched pv-plane builders vs the per-batch
reference, prefetched multi-day training parity (including under fault
injection), and the parallel-pack speedup floor.

The contract under test: FLAGS_pass_pack_threads and FLAGS_pass_prefetch
change WALL CLOCK only — every plane, every loss, and the final table
state are bit-identical to the serial single-threaded pass loop.
"""

import threading
import time

import numpy as np
import pytest

from paddlebox_tpu import flags
from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.data import pass_feed as pf
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.prefetch import PassPrefetcher
from paddlebox_tpu.data.rank_offset import (build_ads_offset,
                                            build_ads_offset_batched,
                                            build_rank_offset,
                                            build_rank_offset_batched)
from paddlebox_tpu.data.slot_record import SlotRecordBlock
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.ps.embedding import PassKeyMapper
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.trainer.trainer import SparseTrainer
from paddlebox_tpu.utils.monitor import StatRegistry, stat_get, stat_snapshot

S, CAP, D = 5, 3, 4


# ---------------------------------------------------------------------------
# Pack bit-identity: 1 thread vs 4 threads, full plane surface.
# ---------------------------------------------------------------------------

def _rich_cfg(pv: bool) -> DataFeedConfig:
    """Every optional plane at once: uid slot, InputTable aux slot, and
    (pv variants) rank_offset + ads_offset."""
    extra = dict(rank_offset=True, ads_offset=True) if pv else {}
    return DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=D),
         SlotConfig("user", dtype="string", capacity=2)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=CAP)
           for i in range(S)]), uid_slot="s0", **extra)


def _rich_block(rng, n, n_keys=400, pv=False) -> SlotRecordBlock:
    blk = SlotRecordBlock(n=n)
    for i in range(S):
        lens = rng.integers(1, CAP + 1, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        blk.uint64_slots[f"s{i}"] = (
            rng.integers(1, n_keys, size=int(off[-1])).astype(np.uint64), off)
    blk.float_slots["label"] = (rng.integers(0, 2, n).astype(np.float32),
                                np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (
        rng.normal(0, 1, n * D).astype(np.float32),
        np.arange(n + 1, dtype=np.int64) * D)
    lens = rng.integers(1, 3, size=n)
    off = np.zeros((n + 1,), np.int64)
    np.cumsum(lens, out=off[1:])
    blk.aux_slots["user"] = (
        rng.integers(1, 50, size=int(off[-1])).astype(np.int32), off)
    if pv:
        blk.search_ids = np.sort(
            rng.integers(0, n // 2 + 1, size=n).astype(np.uint64))
        blk.cmatch = rng.choice([222, 223, 224, 0], size=n).astype(np.int32)
        blk.rank = rng.integers(0, 5, size=n).astype(np.int32)
    return blk


_FIELDS = ("indices", "lengths", "dense", "labels", "valid", "uid",
           "rank_offset", "ads_offset", "batch_real", "batch_base")


@pytest.mark.parametrize("variant", ["dense", "prebatched", "counts"])
def test_parallel_pack_bit_identical(variant):
    """pack_pass at 4 threads == pack_pass at 1 thread, byte for byte,
    on every plane it produces — the disjoint-row-writes argument holds
    across the dense, prebatched, and batch_counts partitions."""
    pv = variant != "dense"
    cfg = _rich_cfg(pv)
    B = 32
    if variant == "dense":
        blocks = [_rich_block(np.random.default_rng(s), 70 + 13 * s)
                  for s in range(3)]
        kwargs = {}
    else:
        ds = SlotDataset(cfg)
        ds._blocks = [_rich_block(np.random.default_rng(9), 150, pv=True)]
        ds.preprocess_instance()
        if variant == "prebatched":
            blocks = list(ds.batches(B))
            kwargs = {"prebatched": True}
        else:
            blocks = ds.get_blocks()
            kwargs = {"batch_counts": [hi - lo
                                       for lo, hi in ds.batch_bounds(B)]}
    keys = np.unique(np.concatenate(
        [v[0] for b in blocks for v in b.uint64_slots.values()]))
    mapper = PassKeyMapper(keys[keys != 0])

    a1 = pf.pack_pass(blocks, cfg, B, key_mapper=mapper, pack_threads=1,
                      **kwargs)
    planes = []
    a4 = pf.pack_pass(blocks, cfg, B, key_mapper=mapper, pack_threads=4,
                      on_plane=lambda name, a: planes.append(name), **kwargs)

    for f in _FIELDS:
        x, y = getattr(a1, f), getattr(a4, f)
        if x is None:
            assert y is None, f
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"field {f!r}")
    assert a1.aux is not None and set(a1.aux) == set(a4.aux) == {"user"}
    np.testing.assert_array_equal(a1.aux["user"], a4.aux["user"])
    # the H2D overlap hook saw every device-bound plane exactly once
    want = {"indices", "lengths", "dense", "labels", "valid", "user"}
    if pv:
        want |= {"rank_offset", "ads_offset"}
    assert set(planes) == want and len(planes) == len(want)


def test_pack_thread_count_flag_is_transparent():
    """pack_threads=None reads FLAGS_pass_pack_threads; flipping the flag
    must not change a single byte either."""
    cfg = _rich_cfg(pv=False)
    blocks = [_rich_block(np.random.default_rng(3), 90)]
    keys = np.unique(np.concatenate(
        [v[0] for v in blocks[0].uint64_slots.values()]))
    mapper = PassKeyMapper(keys[keys != 0])
    prev = flags.get_flags("pass_pack_threads")
    try:
        flags.set_flags({"pass_pack_threads": 1})
        a1 = pf.pack_pass(blocks, cfg, 16, key_mapper=mapper)
        flags.set_flags({"pass_pack_threads": 4})
        a4 = pf.pack_pass(blocks, cfg, 16, key_mapper=mapper)
    finally:
        flags.set_flags({"pass_pack_threads": prev})
    np.testing.assert_array_equal(a1.indices, a4.indices)
    np.testing.assert_array_equal(a1.lengths, a4.lengths)
    np.testing.assert_array_equal(a1.dense, a4.dense)


# ---------------------------------------------------------------------------
# Batched pv-plane builders vs the per-batch reference loop.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_rank_ads_match_per_batch(seed):
    """The whole-pass vectorized builders reproduce the per-batch loop
    bit for bit — including empty batches, full batches, and pv runs
    touching the batch boundary."""
    rng = np.random.default_rng(seed)
    B = 8
    counts = [5, 8, 0, 3, 1, 8]         # empty + full batches included
    batch_real = np.asarray(counts, np.int64)
    batch_base = np.concatenate([[0], np.cumsum(batch_real)[:-1]])
    m = int(batch_real.sum())
    # pv runs contiguous within each batch (pv-aligned cuts never split a
    # pv); distinct id ranges per batch keep the fixture honest
    sid = np.concatenate([
        np.sort(rng.integers(0, 4, size=c).astype(np.uint64)) + 100 * i
        for i, c in enumerate(counts)]).astype(np.uint64)
    cm = rng.choice([222, 223, 224, 0], size=m).astype(np.int32)
    rk = rng.integers(0, 6, size=m).astype(np.int32)

    got_r = build_rank_offset_batched(sid, cm, rk, batch_real, batch_base, B)
    got_a = build_ads_offset_batched(sid, batch_real, batch_base, B)
    want_r = np.full_like(got_r, -1)
    for i, c in enumerate(counts):
        b0 = int(batch_base[i])
        want_r[i * B:(i + 1) * B] = build_rank_offset(
            sid[b0:b0 + c], cm[b0:b0 + c], rk[b0:b0 + c], B)
        np.testing.assert_array_equal(
            got_a[i], build_ads_offset(sid[b0:b0 + c], c, B),
            err_msg=f"ads_offset batch {i}")
    np.testing.assert_array_equal(got_r, want_r)

    # no pv data parsed -> all -1, same as the per-batch builder
    none_r = build_rank_offset_batched(None, None, None,
                                       batch_real, batch_base, B)
    assert none_r.shape == got_r.shape and np.all(none_r == -1)


# ---------------------------------------------------------------------------
# Prefetched multi-day training parity.
# ---------------------------------------------------------------------------

N_DAYS, N_PASSES, B = 2, 3, 32


def _simple_cfg():
    return DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=3)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=CAP)
           for i in range(4)]))


def _simple_block(rng, n, n_keys=500):
    blk = SlotRecordBlock(n=n)
    for i in range(4):
        lens = rng.integers(1, CAP + 1, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        blk.uint64_slots[f"s{i}"] = (
            rng.integers(1, n_keys, size=int(off[-1])).astype(np.uint64), off)
    blk.float_slots["label"] = (rng.integers(0, 2, n).astype(np.float32),
                                np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (
        rng.normal(0, 1, n * 3).astype(np.float32),
        np.arange(n + 1, dtype=np.int64) * 3)
    return blk


def _mk_ds(cfg, day, p):
    ds = SlotDataset(cfg)
    ds._blocks = [_simple_block(np.random.default_rng(100 * day + 10 * p),
                                96)]
    return ds


def _day_keys(cfg):
    parts = []
    for day in range(N_DAYS):
        for p in range(N_PASSES):
            for b in _mk_ds(cfg, day, p).get_blocks():
                parts.append(b.all_keys())
    return np.unique(np.concatenate(parts))


def _engine_trainer(table=None):
    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=4, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)), seed=0)
    if table is not None:
        eng.table = table
    model = DeepFM(num_slots=4, emb_width=3 + 4, dense_dim=3, hidden=(8,))
    tr = SparseTrainer(eng, model, _simple_cfg(), batch_size=B, seed=0,
                       sparse_path="fast")
    return eng, tr


def _run_days(prefetch: bool, table=None, wrap=None):
    """2 days x 3 passes of real DeepFM training; serial pass loop or the
    PassPrefetcher driving the same deterministic per-pass datasets.
    ``wrap(eng, tr)`` may hook the pair before the first pass."""
    eng, tr = _engine_trainer(table)
    if wrap is not None:
        wrap(eng, tr)
    return _drive_days(eng, tr, prefetch)


def _drive_days(eng, tr, prefetch: bool):
    cfg = _simple_cfg()
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
        _submit_days(pre, eng)
        for _ in range(N_DAYS * N_PASSES):
            feed = pre.next_pass()
            losses.append(tr.train_pass(feed)["loss"])
            pre.end_pass()          # wakes the worker's day-boundary gate
    finally:
        pre.close()
    return losses, eng, tr


def _submit_days(pre, eng):
    cfg = _simple_cfg()
    for day in range(N_DAYS):
        for p in range(N_PASSES):
            def load(day=day, p=p):
                ds = _mk_ds(cfg, day, p)
                for b in ds.get_blocks():
                    eng.add_keys(b.all_keys())
                return ds
            pre.submit(load, tag=f"d{day}p{p}", date=f"2026080{day + 1}")


def _assert_runs_identical(a, b, keys):
    losses1, eng1, tr1 = a
    losses2, eng2, tr2 = b
    np.testing.assert_array_equal(np.asarray(losses1), np.asarray(losses2))
    s1, s2 = eng1.table.bulk_pull(keys), eng2.table.bulk_pull(keys)
    assert set(s1) == set(s2)
    for f in s1:
        np.testing.assert_array_equal(np.asarray(s1[f]), np.asarray(s2[f]),
                                      err_msg=f"table field {f!r}")
    import jax
    for p1, p2 in zip(jax.tree_util.tree_leaves(tr1.params),
                      jax.tree_util.tree_leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_prefetched_day_loop_bit_identical():
    """The whole pipelined path — worker-side feed/pull/pack against
    peek_next_mapper, main-thread adopt+upload, day-boundary drain before
    end_day decay — reproduces the serial loop exactly: same per-pass
    losses, same model params, same final table, both days."""
    keys = _day_keys(_simple_cfg())
    _assert_runs_identical(_run_days(prefetch=False),
                           _run_days(prefetch=True), keys)


def test_prefetched_chaos_day_bit_identical():
    """Pipelining composes with the exactly-once PS protocol: the same
    2-day workflow against a remote table under seeded connection chaos
    (drops + delays on client send/recv) converges bit-identically to the
    fault-free serial run."""
    from paddlebox_tpu.ps import faults
    from paddlebox_tpu.ps.host_table import ShardedHostTable
    from paddlebox_tpu.ps.service import PSClient, PSServer, \
        RemoteTableAdapter

    tcfg = EmbeddingTableConfig(embedding_dim=4, shard_num=4,
                                sgd=SparseSGDConfig(mf_create_thresholds=0.0))
    keys = _day_keys(_simple_cfg())
    flags.set_flags({"ps_fault_injection": True})
    srv1 = srv2 = None
    try:
        table1 = ShardedHostTable(tcfg, seed=0)
        srv1 = PSServer(table1)
        client1 = PSClient(srv1.addr, retries=None, retry_sleep=0.01,
                           backoff_cap=0.1, deadline=60)
        want = _run_days(prefetch=False,
                         table=RemoteTableAdapter(client1, delta_mode=True))

        table2 = ShardedHostTable(tcfg, seed=0)
        srv2 = PSServer(table2)
        client2 = PSClient(srv2.addr, retries=None, retry_sleep=0.01,
                           backoff_cap=0.1, deadline=60)
        faults.install(
            faults.FaultPlan(seed=17)
            .drop("send", role="client", prob=0.04)
            .drop("recv", role="client", prob=0.03)
            .delay("send", 0.002, role="client", prob=0.1))
        got = _run_days(prefetch=True,
                        table=RemoteTableAdapter(client2, delta_mode=True))
        faults.uninstall()

        losses1, _, tr1 = want
        losses2, _, tr2 = got
        np.testing.assert_array_equal(np.asarray(losses1),
                                      np.asarray(losses2))
        s1, s2 = table1.bulk_pull(keys), table2.bulk_pull(keys)
        for f in s1:
            np.testing.assert_array_equal(s1[f], s2[f],
                                          err_msg=f"table field {f!r}")
    finally:
        faults.uninstall()
        flags.set_flags({"ps_fault_injection": False})
        for srv in (srv1, srv2):
            if srv is not None:
                srv.shutdown()


def _write_slot_file(path, rng, n):
    with open(path, "w") as f:
        for _ in range(n):
            parts = [f"1 {rng.integers(0, 2)}",
                     "3 " + " ".join(f"{rng.normal():.4f}"
                                     for _ in range(3))]
            for _s in range(4):
                k = rng.integers(1, CAP + 1)
                parts.append(f"{k} " + " ".join(
                    str(rng.integers(1, 500)) for _ in range(k)))
            f.write(" ".join(parts) + "\n")


@pytest.mark.parametrize("n_passes", [2, 5])
def test_fleet_train_passes_parity(n_passes, tmp_path):
    """fleet.train_passes — the user-level day loop — trains identically
    with the prefetcher on and off over real files: two seeded passes of
    other lengths in turn, each after the first parsed into the storage
    the last one's blocks held (the dataset's BlockStore; the prefetch
    worker hands it back at its next load, after its own pack), and
    packed into the planes an earlier pass's feed was uploaded from (the
    trainer's PlaneStore; a pass hands them back when it has trained)."""
    from paddlebox_tpu import fleet
    from paddlebox_tpu.native import slot_parser
    from tests.test_data_pipeline import ScribblingStore
    from tests.test_pass_feed import ScribblingPlaneStore

    cfg = _simple_cfg()
    files = []
    for p in range(2):
        path = str(tmp_path / f"p{p}.txt")
        _write_slot_file(path, np.random.default_rng(p), 64 + 23 * p)
        files.append([path])
    passes = [files[k % 2] for k in range(n_passes)]
    keys = np.arange(1, 500, dtype=np.uint64)   # all _write_slot_file draws

    def run(prefetch):
        StatRegistry.instance().reset()
        eng = BoxPSEngine(EmbeddingTableConfig(
            embedding_dim=4, shard_num=4,
            sgd=SparseSGDConfig(mf_create_thresholds=0.0)), seed=0)
        ds = fleet.BoxPSDataset(cfg, engine=eng, read_threads=1)
        # whoever still reads a block after it was handed back sees 0xFF
        ds.dataset._store = ScribblingStore()
        model = DeepFM(num_slots=4, emb_width=3 + 4, dense_dim=3,
                       hidden=(8,))
        tr = SparseTrainer(eng, model, cfg, batch_size=32, seed=0,
                           sparse_path="fast")
        # and whoever still reads a host plane after its pass has trained
        tr._plane_store = ScribblingPlaneStore()
        metrics = fleet.train_passes(tr, ds, passes, date="20260801",
                                     prefetch=prefetch)
        return (metrics, eng, tr, stat_snapshot("data.read"),
                stat_snapshot("data.pack"))

    serial, pipe = run(False), run(True)
    assert len(serial[0]) == len(pipe[0]) == n_passes
    np.testing.assert_array_equal([m["batches"] for m in serial[0]],
                                  [m["batches"] for m in pipe[0]])
    _assert_runs_identical(([m["loss"] for m in serial[0]], *serial[1:3]),
                           ([m["loss"] for m in pipe[0]], *pipe[1:3]), keys)
    if slot_parser.available():
        for stats in (serial[3], pipe[3]):
            # the first read is fresh; the second is too if no slab of the
            # first is large enough; every later one is a reuse
            reused = stats.get("data.read.block_bytes_reused", 0)
            fresh = stats["data.read.block_bytes_fresh"]
            assert reused / (reused + fresh) >= (n_passes - 2) / n_passes
    # the two passes are of other shapes (two batches of 32, three): at
    # most the first two packs allocate (a buffer is a page at least, so
    # the second may fit some of the first's), every later one finds its
    # planes kept
    per_pass = [nb * (4 * CAP * 4 + 4 * 4 + 3 * 4 + 4 + 1)
                for nb in ([64, 96] * n_passes)[:n_passes]]
    for stats in (serial[4], pipe[4]):
        fresh = stats["data.pack.plane_bytes_fresh"]
        reused = stats.get("data.pack.plane_bytes_reused", 0)
        assert fresh + reused == sum(per_pass)
        assert per_pass[0] <= fresh <= sum(per_pass[:2])


def test_prefetch_failure_surfaces_at_next_pass():
    """A worker-side load failure must fail that next_pass loudly — never
    silently train a stale working set."""
    cfg = _simple_cfg()
    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=4, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)))
    model = DeepFM(num_slots=4, emb_width=3 + 4, dense_dim=3, hidden=(8,))
    tr = SparseTrainer(eng, model, cfg, batch_size=B, seed=0,
                       sparse_path="fast")

    def boom():
        raise OSError("filesystem went away")

    with PassPrefetcher(eng, tr) as pre:
        pre.submit(boom, tag="doomed")
        with pytest.raises(RuntimeError, match="prefetch failed"):
            pre.next_pass()


# ---------------------------------------------------------------------------
# Pack beside pull: the worker packs while the table pull still runs.
# ---------------------------------------------------------------------------

_PLANES = ("indices", "lengths", "dense", "labels", "valid")
_HOLD_S = 20.0


def _wait(event, what):
    if not event.wait(_HOLD_S):
        raise TimeoutError(what)


class _HeldPullTable:
    """A host table whose pulls off the main thread (the engine's build
    thread) first call ``hold``: a test's hook to order the pull against
    the pack.  Main-thread pulls (the stale-row refresh, the test's own
    reads) go straight through."""

    def __init__(self, inner, hold):
        self._inner = inner
        self._hold = hold

    def bulk_pull(self, keys):
        if threading.current_thread() is not threading.main_thread():
            self._hold()
        return self._inner.bulk_pull(keys)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _hook_pack(tr, before=None, packs=None):
    """Run ``before()`` as each pack begins; copy each pack's planes into
    ``packs`` (a later pass may reuse their buffers)."""
    orig = tr.pack_pass_host

    def pack(dataset, mapper=None, **kw):
        if before is not None:
            before()
        arrays = orig(dataset, mapper=mapper, **kw)
        if packs is not None:
            packs.append({f: np.array(getattr(arrays, f)) for f in _PLANES})
        return arrays

    tr.pack_pass_host = pack


def test_pack_begins_before_the_pull_ends():
    """Every pass's table pull waits until that pass's pack has begun, an
    order in which packing after the pull would deadlock: the prefetched
    days still pack the serial loop's planes byte for byte, train to the
    same losses, params and table, and count each pass as packed beside
    its pull."""
    packing = threading.Event()

    def pull_after_pack():
        _wait(packing, "the pull waited for a pack that never began")
        packing.clear()

    serial_packs, pipe_packs = [], []

    def gate(eng, tr):
        eng.table = _HeldPullTable(eng.table, pull_after_pack)
        _hook_pack(tr, before=packing.set, packs=pipe_packs)

    StatRegistry.instance().reset()
    pipe = _run_days(prefetch=True, wrap=gate)
    beside = stat_get("data.prefetch.pack_beside_pull")
    serial = _run_days(prefetch=False,
                       wrap=lambda eng, tr: _hook_pack(tr, packs=serial_packs))
    assert beside == N_DAYS * N_PASSES
    assert len(serial_packs) == len(pipe_packs) == N_DAYS * N_PASSES
    for i, (want, got) in enumerate(zip(serial_packs, pipe_packs)):
        for f in _PLANES:
            np.testing.assert_array_equal(want[f], got[f],
                                          err_msg=f"pass {i} plane {f!r}")
    _assert_runs_identical(serial, pipe, _day_keys(_simple_cfg()))


def test_pull_failure_beside_the_pack_fails_that_pass():
    """A pull that raises while its pass packs fails THAT pass at
    next_pass, with the prefetch failure chained to the pull's error, and
    the engine never adopts it."""
    packing = threading.Event()

    def pull_then_fail():
        _wait(packing, "the pull waited for a pack that never began")
        raise ConnectionError("table shard went away")

    eng, tr = _engine_trainer()
    eng.table = _HeldPullTable(eng.table, pull_then_fail)

    def pack_while_the_pull_fails():
        packing.set()
        deadline = time.monotonic() + _HOLD_S
        while eng.feed_build_running() and time.monotonic() < deadline:
            time.sleep(0.005)

    _hook_pack(tr, before=pack_while_the_pull_fails)
    begun = []
    begin_pass = eng.begin_pass
    eng.begin_pass = lambda: (begun.append(1), begin_pass())
    with PassPrefetcher(eng, tr) as pre:
        _submit_days(pre, eng)
        with pytest.raises(RuntimeError, match="prefetch failed") as ei:
            pre.next_pass()
    assert isinstance(ei.value.__cause__.__cause__, ConnectionError)
    assert begun == [] and eng.pass_id == 0


def test_abort_mid_pack_with_the_pull_in_flight():
    """Crash teardown while the worker packs and the pull still runs:
    abort joins both, leaves the engine at a clean pass boundary (no
    pending build, mapper or working set), and the same engine and
    trainer then re-drive both days bit-identically to the serial loop."""
    aborting, pack_began = threading.Event(), threading.Event()

    def pull_until_abort():
        _wait(aborting, "the test never aborted")
        time.sleep(0.05)        # still pulling while abort joins the worker

    def pack_until_abort():
        pack_began.set()
        _wait(aborting, "the test never aborted")

    eng, tr = _engine_trainer()
    table = eng.table
    eng.table = _HeldPullTable(table, pull_until_abort)
    _hook_pack(tr, before=pack_until_abort)
    pre = PassPrefetcher(eng, tr)
    _submit_days(pre, eng)
    _wait(pack_began, "the worker never began to pack")
    assert eng.feed_build_running()
    aborting.set()
    pre.abort()
    assert not eng.feed_build_running()
    assert eng.peek_next_mapper() is None
    assert eng.ws is None and eng.pass_id == 0

    eng.table = table
    del tr.pack_pass_host
    _assert_runs_identical(_run_days(prefetch=False),
                           _drive_days(eng, tr, prefetch=True),
                           _day_keys(_simple_cfg()))


# ---------------------------------------------------------------------------
# Parallel pack at a pass-sized block.
# ---------------------------------------------------------------------------

def test_parallel_pack_fans_out_at_pass_size():
    """At a pass-sized block the 4-thread pack really runs on the pool
    (its task and concurrency counters move; 1 thread stays inline) and
    lands on the 1-thread planes byte for byte.  How much faster it is
    is a question for the chip's host, not for a CPU six test workers
    share."""
    rng = np.random.default_rng(6)
    cfg = DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=4)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=3)
           for i in range(8)]))
    blk = SlotRecordBlock(n=60_000)
    n = blk.n
    for i in range(8):
        lens = rng.integers(1, 4, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        blk.uint64_slots[f"s{i}"] = (
            rng.integers(1, 200_000, size=int(off[-1])).astype(np.uint64),
            off)
    blk.float_slots["label"] = (rng.integers(0, 2, n).astype(np.float32),
                                np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (
        rng.normal(0, 1, n * 4).astype(np.float32),
        np.arange(n + 1, dtype=np.int64) * 4)
    keys = np.unique(np.concatenate(
        [v[0] for v in blk.uint64_slots.values()]))
    mapper = PassKeyMapper(keys[keys != 0])

    def pack(threads):
        StatRegistry.instance().reset()
        arrays = pf.pack_pass([blk], cfg, 4096, key_mapper=mapper,
                              pack_threads=threads)
        return arrays, stat_snapshot("ps.pool.pack.")

    a1, pool1 = pack(1)
    a4, pool4 = pack(4)
    assert pool1.get("ps.pool.pack.tasks", 0) == 0
    assert pool4["ps.pool.pack.tasks"] >= 4
    assert pool4["ps.pool.pack.active_hwm"] >= 2
    for f in ("indices", "lengths", "dense", "labels", "valid"):
        np.testing.assert_array_equal(getattr(a1, f), getattr(a4, f),
                                      err_msg=f"field {f!r}")
