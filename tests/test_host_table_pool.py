"""Shard-parallel host-table execution engine (utils/workpool.py +
ps/host_table.py): bit-identity across pool sizes, capacity-doubling
growth amortization, concurrent pull/upsert stress, the pooled-table
chaos day (composes with the exactly-once retry protocol), delta-save
atomicity, lock-wait observability, pool metrics in /statz and the
per-pass report, and the pull+write fan-out at 4 threads.
"""

import os
import threading

import numpy as np
import pytest

from paddlebox_tpu import flags
from paddlebox_tpu.config import (AccessorConfig, EmbeddingTableConfig,
                                  SparseSGDConfig)
from paddlebox_tpu.ps.host_table import ShardedHostTable
from paddlebox_tpu.utils import workpool
from paddlebox_tpu.utils.monitor import StatRegistry, stat_snapshot

_DEFAULT_THREADS = min(8, os.cpu_count() or 1)


@pytest.fixture(autouse=True)
def _pool_reset():
    StatRegistry.instance().reset()
    yield
    flags.set_flags({"ps_table_threads": _DEFAULT_THREADS})
    workpool.table_pool()


def set_threads(n: int) -> None:
    flags.set_flags({"ps_table_threads": n})
    assert workpool.table_pool().threads == max(1, n)


def make_table(shard_num=8, dim=8, seed=7, **acc):
    return ShardedHostTable(EmbeddingTableConfig(
        embedding_dim=dim, shard_num=shard_num,
        accessor=AccessorConfig(**acc)), seed=seed)


def table_state(t: ShardedHostTable):
    """Exact per-shard state: (keys, soa) copies in shard order."""
    out = []
    for s in t._shards:
        with s.lock:
            out.append((s.keys.copy(),
                        {f: v.copy() for f, v in s.soa.items()}))
    return out


def assert_states_equal(a, b):
    assert len(a) == len(b)
    for (ka, sa), (kb, sb) in zip(a, b):
        np.testing.assert_array_equal(ka, kb)
        assert set(sa) == set(sb)
        for f in sa:
            np.testing.assert_array_equal(sa[f], sb[f], err_msg=f)


def drive_workload(t: ShardedHostTable, tmp_path=None):
    """A deterministic multi-phase workload touching every pooled verb."""
    rng = np.random.default_rng(0)
    pulls = []
    for step in range(4):
        keys = np.unique(rng.integers(1, 5000, 600).astype(np.uint64))
        rows = t.bulk_pull(keys)
        pulls.append({f: v.copy() for f, v in rows.items()})
        rows["show"] += np.float32(step + 1)
        rows["click"] += np.float32(1.0)
        rows["mf"] += np.float32(0.25)
        rows["unseen_days"][:] = 0.0
        t.bulk_write(keys, rows)
    t.end_day()
    removed = t.shrink()
    if tmp_path is not None:
        saved = t.save(str(tmp_path), mode="all")
        t2 = make_table(shard_num=t.shard_num, dim=t.mf_dim)
        loaded = t2.load(str(tmp_path))
        assert loaded == saved == t.size()
        assert_states_equal(table_state(t), table_state(t2))
    return pulls, removed


def test_pool_sizes_bit_identical(tmp_path):
    """The whole verb surface — pull/write/end_day/shrink/save/load —
    produces bit-identical tables and pulls at pool size 1 vs N."""
    set_threads(1)
    t1 = make_table(delete_threshold=0.05)
    pulls1, removed1 = drive_workload(t1, tmp_path / "seq")
    state1 = table_state(t1)

    set_threads(4)
    t4 = make_table(delete_threshold=0.05)
    pulls4, removed4 = drive_workload(t4, tmp_path / "par")
    assert removed1 == removed4
    for p1, p4 in zip(pulls1, pulls4):
        for f in p1:
            np.testing.assert_array_equal(p1[f], p4[f], err_msg=f)
    assert_states_equal(state1, table_state(t4))


def test_growth_amortized_append():
    """Repeated-pass upsert of fresh keys must NOT reallocate every SoA
    array per call: capacity doubling keeps reallocations O(log rows)."""
    t = make_table(shard_num=4, dim=4)
    calls = 200
    for step in range(calls):
        keys = np.arange(step * 256 + 1, (step + 1) * 256 + 1, dtype=np.uint64)
        rows = t.bulk_pull(keys)
        t.bulk_write(keys, rows)
    grows, appends = t.grow_stats()
    assert appends == calls * t.shard_num       # every call appended
    # the old np.concatenate path reallocated once per append call; the
    # doubling buffers need ~log2(rows_per_shard / 64) reallocations
    assert grows <= t.shard_num * 16, (grows, appends)
    assert grows < appends / 8
    # buffers stay consistent: views match logical size, capacity >= size
    for s in t._shards:
        assert len(s.keys) == s.size <= s.capacity
        for f, v in s.soa.items():
            assert len(v) == s.size, f


def test_overwrite_only_upsert_never_grows():
    t = make_table(shard_num=2, dim=4)
    keys = np.arange(1, 1001, dtype=np.uint64)
    rows = t.bulk_pull(keys)
    t.bulk_write(keys, rows)
    grows0, _ = t.grow_stats()
    for _ in range(20):                      # pure overwrites
        rows["show"] += 1.0
        t.bulk_write(keys, rows)
    grows1, _ = t.grow_stats()
    assert grows1 == grows0
    np.testing.assert_allclose(
        t.bulk_pull(keys)["show"], rows["show"])


def test_concurrent_preload_pull_vs_upsert_stress():
    """The pipelined engine's shape: a preload thread bulk_pulls while the
    main thread bulk_writes — through a real multi-thread pool.  The final
    table must hold exactly the written values, and every pull must return
    internally consistent rows (never a torn row)."""
    set_threads(4)
    t = make_table(shard_num=8, dim=8)
    rng = np.random.default_rng(1)
    stop = threading.Event()
    errors = []

    def puller():
        prng = np.random.default_rng(2)
        try:
            while not stop.is_set():
                keys = np.unique(
                    prng.integers(1, 20_000, 512).astype(np.uint64))
                rows = t.bulk_pull(keys)
                # written rows always carry show == click (the writer's
                # invariant below); fresh defaults carry 0 == 0
                np.testing.assert_array_equal(rows["show"], rows["click"])
        except Exception as e:  # surfaced after join
            errors.append(e)

    th = threading.Thread(target=puller, daemon=True)
    th.start()
    written = {}
    for step in range(30):
        keys = np.unique(rng.integers(1, 20_000, 512).astype(np.uint64))
        rows = t.bulk_pull(keys)
        val = np.float32(step + 1)
        rows["show"][:] = val
        rows["click"][:] = val
        t.bulk_write(keys, rows)
        for k in keys.tolist():
            written[k] = val
    stop.set()
    th.join(timeout=30)
    assert not th.is_alive() and not errors, errors
    all_keys = np.array(sorted(written), np.uint64)
    back = t.bulk_pull(all_keys)
    np.testing.assert_array_equal(
        back["show"], np.array([written[k] for k in all_keys.tolist()],
                               np.float32))
    # pool-induced queueing on hot shards is now visible: lock WAIT
    # histograms sit beside the hold-time ones
    snap = stat_snapshot("ps.host_table")
    assert snap.get("ps.host_table.pull_lock_wait_s.count", 0) > 0
    assert snap.get("ps.host_table.write_lock_wait_s.count", 0) > 0
    assert snap.get("ps.host_table.write_lock_hold_s.count", 0) > 0


def test_ssd_fault_in_pooled_matches_sequential(tmp_path):
    """Spill + batched fault-in through the pool vs sequentially: same
    promoted rows, same values, same residency split."""
    from paddlebox_tpu.ps.ssd_table import SSDTieredTable

    def run(threads, sub):
        set_threads(threads)
        host = make_table(shard_num=8, dim=4)
        tiered = SSDTieredTable(host, str(tmp_path / sub))
        keys = np.arange(1, 2001, dtype=np.uint64)
        rows = host.bulk_pull(keys)
        rows["show"][:1000] = 0.1
        rows["show"][1000:] = 100.0
        host.bulk_write(keys, rows)
        spilled = tiered.spill(score_threshold=1.0)
        pull = tiered.bulk_pull(np.arange(1, 2001, 7, dtype=np.uint64))
        return spilled, host.size(), tiered.total_size(), pull

    s1, h1, t1, p1 = run(1, "seq")
    s4, h4, t4, p4 = run(4, "par")
    assert (s1, h1, t1) == (s4, h4, t4)
    for f in p1:
        np.testing.assert_array_equal(p1[f], p4[f], err_msg=f)


def test_delta_save_is_atomic_per_shard(tmp_path):
    """A mid-save filesystem failure must not lose deltas: each shard
    writes to a tmp name + renames, and delta_score resets only after its
    shard file landed."""
    from paddlebox_tpu.io import fs as pfs

    set_threads(1)                 # deterministic failure ordering
    t = make_table(shard_num=4, dim=4, delta_threshold=0.0)
    keys = np.arange(1, 401, dtype=np.uint64)
    rows = t.bulk_pull(keys)
    rows["delta_score"][:] = 3.0
    rows["show"][:] = 5.0
    t.bulk_write(keys, rows)

    broken = "part-00002"

    class FailingFS(pfs.LocalFS):
        @staticmethod
        def _strip(path):
            if path.startswith("failfs://"):
                path = path[len("failfs://"):]
            return pfs.LocalFS._strip(path)

        def open_write(self, path):
            if broken in path:
                raise IOError("disk full (injected)")
            return super().open_write(path)

    pfs.register_fs("failfs", FailingFS())
    try:
        with pytest.raises(IOError, match="disk full"):
            t.save(f"failfs://{tmp_path}/delta", mode="delta")
    finally:
        pfs.register_fs("failfs", pfs.LocalFS())  # defuse for other users
    # the failed shard kept its deltas; no torn shard file is visible
    assert not os.path.exists(
        str(tmp_path / "delta" / f"{broken}.shard.npz"))
    failed_shard = t._shards[2]
    assert (failed_shard.soa["delta_score"] == 3.0).all()
    # shards whose file landed DID reset (write happened before the fail)
    landed = [i for i in range(4) if i != 2 and t._shards[i].size]
    assert any((t._shards[i].soa["delta_score"] == 0.0).all()
               for i in landed)
    # a clean retry completes and leaves no tmp litter
    n = t.save(str(tmp_path / "delta2"), mode="delta")
    assert n > 0
    files = sorted(os.listdir(tmp_path / "delta2"))
    assert files and all(f.endswith(".shard.npz") for f in files)
    for s in t._shards:
        assert (s.soa["delta_score"] == 0.0).all()


def test_pool_metrics_in_statz_and_pass_report():
    """Queue-depth/utilization metrics reach /statz and the per-pass
    report (the acceptance surface of the PR 4 observability fold-in)."""
    import json
    import urllib.request

    from paddlebox_tpu.ps.pass_manager import BoxPSEngine
    from paddlebox_tpu.utils import obs_server

    set_threads(4)
    eng = BoxPSEngine(EmbeddingTableConfig(embedding_dim=4, shard_num=8))
    eng.begin_feed_pass()
    eng.add_keys(np.arange(1, 4001, dtype=np.uint64))
    eng.end_feed_pass()
    eng.begin_pass()
    eng.ws["show"] = eng.ws["show"] + 1.0
    eng.end_pass()

    report = eng.pass_report()
    assert "pool table:" in report
    assert "queue_hwm=" in report and "busy=" in report

    srv = obs_server.ObsServer(port=0)
    try:
        url = f"http://127.0.0.1:{srv.addr[1]}/statz"
        with urllib.request.urlopen(url, timeout=5) as resp:
            snap = json.loads(resp.read().decode())
    finally:
        srv.shutdown()
    assert snap.get("ps.pool.table.tasks", 0) > 0
    assert "ps.pool.table.queue_depth_hwm" in snap
    assert "ps.pool.table.utilization.p95" in snap
    assert snap.get("ps.pool.table.threads") == 4.0


def test_chaos_day_through_pooled_table():
    """A fast chaos day (in-process fault hooks: dropped acks, delays,
    truncated frames) against a POOLED server table must stay
    bit-identical to the fault-free pooled run — the shard pool composes
    with the exactly-once retry protocol."""
    from paddlebox_tpu.ps import faults
    from paddlebox_tpu.ps.pass_manager import BoxPSEngine
    from paddlebox_tpu.ps.service import PSClient, PSServer, \
        RemoteTableAdapter

    set_threads(4)

    def run_day(plan) -> np.ndarray:
        table = make_table(shard_num=8, dim=4)
        server = PSServer(table)
        client = PSClient(server.addr, retries=None, retry_sleep=0.01,
                          deadline=30.0)
        if plan is not None:
            faults.install(plan)
        try:
            engine = BoxPSEngine(EmbeddingTableConfig(
                embedding_dim=4, shard_num=8))
            engine.table = RemoteTableAdapter(client, delta_mode=True)
            for p in range(3):
                rng = np.random.default_rng(100 + p)
                engine.begin_feed_pass()
                engine.add_keys(np.unique(
                    rng.integers(1, 500, 150).astype(np.uint64)))
                engine.end_feed_pass()
                engine.begin_pass()
                engine.ws["show"] = engine.ws["show"] + float(p + 1)
                engine.ws["mf"] = engine.ws["mf"] + 0.5
                engine.end_pass()
        finally:
            faults.uninstall()
        keys = np.arange(1, 500, dtype=np.uint64)
        out = client.pull_sparse(keys)
        client.close()
        server.shutdown()
        digest = np.concatenate([np.asarray(v, np.float64).ravel()
                                 for _, v in sorted(out.items())])
        return digest

    flags.set_flags({"ps_fault_injection": True})
    try:
        baseline = run_day(None)
        chaos = run_day(faults.FaultPlan.default_chaos(seed=5))
    finally:
        flags.set_flags({"ps_fault_injection": False})
    np.testing.assert_array_equal(baseline, chaos)


def test_pull_write_fans_out_bit_identical():
    """bulk_pull + bulk_write over 8 shards at FLAGS_ps_table_threads=4
    really run on the pool (its task and concurrency counters move; 1
    thread stays inline) and leave bit-identical table state.  How much
    faster that is is a question for the chip's host, not for a CPU six
    test workers share."""
    SHARDS, DIM, N = 8, 32, 200_000
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(1, 2**62, N).astype(np.uint64))

    def build(threads):
        set_threads(threads)
        t = make_table(shard_num=SHARDS, dim=DIM)
        rows = t.bulk_pull(keys)
        t.bulk_write(keys, rows)          # populate (append path)
        return t

    def overwrite(t):
        StatRegistry.instance().reset()
        rows = t.bulk_pull(keys)
        rows["show"] += 1.0
        t.bulk_write(keys, rows)          # steady-state overwrite
        return stat_snapshot("ps.pool.table.")

    t_seq = build(1)
    pool_seq = overwrite(t_seq)
    t_par = build(4)
    pool_par = overwrite(t_par)
    assert_states_equal(table_state(t_seq), table_state(t_par))
    assert pool_seq.get("ps.pool.table.tasks", 0) == 0
    assert pool_par["ps.pool.table.tasks"] >= 2 * SHARDS
    assert pool_par["ps.pool.table.active_hwm"] >= 2
