"""Pass lifecycle engine — the BoxWrapper/BoxHelper equivalent.

≙ BoxWrapper (box_wrapper.h:377) + BoxHelper (box_wrapper.h:1043) + the
open-source PSGPUWrapper pass machinery (ps_gpu_wrapper.cc:114-1007):

  set_date            ≙ BoxHelper::SetDate (box_wrapper.h:1048)
  begin_feed_pass     ≙ BeginFeedPass (box_wrapper.cc:129) — opens a key
                        collection agent for the loading pass
  add_keys            ≙ PSAgent::AddKey via MergeInsKeys (data_set.cc:2293)
  end_feed_pass       ≙ EndFeedPass (box_wrapper.cc:152) — dedups the pass
                        keys (≙ PreBuildTask ps_gpu_wrapper.cc:114), pulls
                        rows from the host table (≙ BuildPull :337) and
                        builds the device working set (≙ BuildGPUTask :684)
  begin_pass/end_pass ≙ box_wrapper.cc:171,186 — end_pass flushes the
                        working set back to the DRAM tier
                        (≙ EndPass dump_pool_to_cpu ps_gpu_wrapper.cc:983)
  save_base/save_delta≙ SaveBase/SaveDelta (box_wrapper.cc:1286)
  load                ≙ InitializeGPUAndLoadModel (box_wrapper.h:624)
  shrink              ≙ ShrinkTable (box_wrapper.h:638)
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np
import jax.numpy as jnp

from paddlebox_tpu import flags
from paddlebox_tpu.config import EmbeddingTableConfig
from paddlebox_tpu.metrics import quality
from paddlebox_tpu.parallel.topology import HybridTopology
from paddlebox_tpu.ps import embedding, faults
from paddlebox_tpu.ps import heat
from paddlebox_tpu.ps.device_cache import CachePlan, DeviceRowCache
from paddlebox_tpu.ps.host_table import ShardedHostTable
from paddlebox_tpu.utils import flight, intervals, lockdep, trace
from paddlebox_tpu.utils.monitor import stat_add, stat_set, stat_snapshot
from paddlebox_tpu.utils.timer import TimerRegistry

flags.define_flag(
    "obs_pass_report", False,
    "print a PrintSyncTimer-style per-pass wall-time table (pull/train/"
    "write seconds, wire bytes, inflight hwm, injected faults) at every "
    "end_pass (≙ PrintSyncTimer box_wrapper.h:795)")


class BoxPSEngine:
    def __init__(self, config: Optional[EmbeddingTableConfig] = None,
                 topology: Optional[HybridTopology] = None, seed: int = 0,
                 mode: str = "train", device_rank: int = 0,
                 device_world: int = 1):
        if mode not in ("train", "serving"):
            raise ValueError(f"mode must be 'train' or 'serving', "
                             f"got {mode!r}")
        self.config = config or EmbeddingTableConfig()
        self.topology = topology
        heat.maybe_enable_from_flags()
        # declared intent, not enforcement: io/checkpoint.py uses it to
        # warn when a serving-only loader (load_xbox) feeds a training
        # engine — the xbox dump cannot round-trip mf_size exactly
        self.mode = mode
        self.table = ShardedHostTable(self.config, seed=seed)
        self.timers = TimerRegistry()
        self.day_id: Optional[str] = None
        self.pass_id = 0
        self.phase = 1  # join/update flip (≙ FlipPhase box_wrapper.h:805)

        self._agent_lock = lockdep.lock("ps.pass_manager.BoxPSEngine._agent_lock")
        self._agent_keys: List[np.ndarray] = []
        self._kept_keys: List[np.ndarray] = []   # keep_keys: in every pass
        self._feeding = False
        # the pass's key dedup: native, scratch kept from pass to pass
        # (False = not tried yet, None = np.unique serves)
        self._key_dedup = False

        self.mapper: Optional[embedding.PassKeyMapper] = None
        self.ws: Optional[Dict[str, jnp.ndarray]] = None
        self.num_keys = 0

        # pass-pipelined preload (≙ PreLoadIntoMemory + pre-build thread,
        # box_wrapper.h:1141 / ps_gpu_wrapper.cc:907-955): the next pass's
        # working set builds in the background while the current one trains
        self._build_thread: Optional[threading.Thread] = None
        self._next: Optional[tuple] = None  # (mapper, n, host_rows, plan)
        # the pending pass's mapper, there from the dedup on (_next[0])
        self._next_mapper: Optional[embedding.PassKeyMapper] = None
        self._last_written: Optional[np.ndarray] = None

        # HBM tier: device-resident hot-row cache (ps/device_cache.py).
        # No longer single-topology-gated: under a sharded PS cluster the
        # cache keys admission by the fleet's ServerMap (attached lazily
        # at the first feed pass — a remote table is wired to the engine
        # AFTER __init__), and per-engine (device_rank, device_world)
        # partitions the cached slice so aggregate cache capacity scales
        # with the mesh instead of every engine caching the same head rows.
        self.device_rank = int(device_rank)
        self.device_world = max(1, int(device_world))
        self.cache: Optional[DeviceRowCache] = None
        self._cache_smap_attached = False
        if mode == "train" and flags.get_flags("ps_device_cache"):
            cap = int(flags.get_flags("ps_device_cache_rows"))
            if cap > 0:
                sgd = self.config.sgd
                self.cache = DeviceRowCache(
                    cap, nonclk_coeff=sgd.nonclk_coeff,
                    clk_coeff=sgd.clk_coeff)
        self._feed_cache_snap = None     # index snapshot for the open feed
        self._cache_fresh_keys = None    # adoption-fresh rows (skip refresh)
        # build_working_set staging-buffer pool (ps.engine.ws_buffer_reuse):
        # adoption/upload is main-thread-only, so one pool per engine
        self._ws_buffers: Dict[str, np.ndarray] = {}

    # -- date / phase --------------------------------------------------------
    def set_date(self, date: str, *, table_decay: bool = True) -> None:
        """Advance the engine's day.  ``table_decay=False`` keeps the
        local day bookkeeping (quality rollover, cache invalidation) but
        skips the ``table.end_day()`` decay — the trainer fleet's mode,
        where exactly ONE rank (the elected leader) drives the decay
        through the 2-phase lifecycle verb and every engine merely
        adopts the new date; N engines each decaying the shared remote
        table would compound the decay N times."""
        if self.day_id is not None and date != self.day_id:
            flight.record("day_end", day=self.day_id, next_day=date)
            if table_decay:
                with self.timers("end_day"):
                    self.table.end_day()
            # day-scale concept-drift rollover (quality.psi.day)
            quality.end_day(self.day_id)
            # coherence point: end_day decayed show/click table-wide —
            # every cached row is stale now (the prefetcher's day-boundary
            # drain guarantees no feed snapshot is in flight here)
            if self.cache is not None:
                self.cache.invalidate("end_day")
            if heat.ACTIVE is not None:
                # heat is per-process telemetry: every engine fades its
                # own sketches at its own day boundary (no N-fold
                # compounding concern — nothing here is shared state)
                heat.ACTIVE.decay_day()
        self.day_id = date

    def flip_phase(self) -> None:
        self.phase = 1 - self.phase

    # -- feed pass -----------------------------------------------------------
    def begin_feed_pass(self) -> None:
        assert not self._feeding, "previous feed pass not closed"
        with self._agent_lock:
            self._agent_keys = list(self._kept_keys)
        # per-pass observability baseline: the end_pass report prints
        # DELTAS against these (wire bytes, faults, timer seconds of this
        # pass only).  Held PENDING until begin_pass promotes it — under
        # pass prefetch, pass N+1's begin_feed_pass runs while pass N is
        # still training, and must not clobber N's open window.
        self._feed_obs0 = {
            # ckpt.* rides along so the per-pass report can show this
            # pass's checkpoint cost next to its wire/train phases
            "stats0": {**stat_snapshot("ps."), **stat_snapshot("ckpt.")},
            "timers0": {n: (s, c) for n, s, c in self.timers.rows()},
            # feed-gap window anchor: end_pass computes the pass's
            # device_busy_frac / feed_gap_ratio over [here, write-back]
            "m0": time.monotonic(),
        }
        flight.record("pass_feed_begin", pass_id=self.pass_id + 1,
                      day=self.day_id)
        # lazy cluster attach: a RemoteTableAdapter over a sharded fleet
        # is wired to the engine after __init__, so adopt its ServerMap
        # for cache admission at the first feed that sees one
        if self.cache is not None and not self._cache_smap_attached:
            smap = getattr(self.table, "server_map", None)
            if smap is not None:
                self.cache.attach_server_map(
                    smap, device_rank=self.device_rank,
                    device_world=self.device_world)
                # elastic fleet: when a fence redirect adopts a newer
                # map, invalidate exactly the moved key range (stale
                # cached rows now belong to a different shard)
                client = getattr(self.table, "client", None)
                if client is not None \
                        and hasattr(client, "on_map_change"):
                    cache = self.cache
                    client.on_map_change(
                        lambda m: cache.update_server_map(
                            m, reason="map_refresh"))
                # pboxlint: disable-next=PB102 -- single-coordinator lifecycle flag
                self._cache_smap_attached = True
        # publish the cache index snapshot for THIS feed (prefetcher-safe:
        # the build thread intersects against this frozen view; authoritative
        # hit resolution re-checks the live index at adoption)
        self._feed_cache_snap = (self.cache.snapshot()
                                 if self.cache is not None else None)
        # the pass lifecycle is driven by one coordinator thread;
        # _agent_lock only guards the add_keys sink
        # pboxlint: disable-next=PB102 -- single-coordinator lifecycle flag
        self._feeding = True

    def add_keys(self, keys: np.ndarray) -> None:
        """Thread-safe feasign sink for dataset reader threads."""
        if len(keys):
            with self._agent_lock:
                self._agent_keys.append(np.asarray(keys, np.uint64))

    def keep_keys(self, keys: np.ndarray) -> None:
        """Keys every feed pass holds from now on, whether its data has
        them or not (a model's tied head reads the rows of its whole
        vocabulary slice: ``SparseTrainer`` hands over the model's
        ``head_keys()``).  They enter through the sink the readers use,
        at ``begin_feed_pass``, and at once where a feed pass is open."""
        keys = np.asarray(keys, np.uint64)
        with self._agent_lock:
            self._kept_keys.append(keys)
        if self._feeding:
            self.add_keys(keys)

    def _native_dedup(self):
        if self._key_dedup is False:
            from paddlebox_tpu.native import build
            dedup = None
            try:
                from paddlebox_tpu.native import hash_map
                if hash_map.available():
                    dedup = hash_map.KeyDedup()
            except Exception as e:  # noqa: BLE001 — np.unique serves
                build.warn_fallback("pass_key_dedup", e)
            # pboxlint: disable-next=PB102 -- set once, by the one thread that runs the feed lifecycle
            self._key_dedup = dedup
        return self._key_dedup

    def _dedup_agent_keys(self) -> np.ndarray:
        with self.timers("dedup_keys"), trace.span("ps.engine.dedup_keys"):
            with self._agent_lock:
                parts = self._agent_keys
                self._agent_keys = []
            n_in = float(sum(len(p) for p in parts))
            stat_add("ps.engine.dedup_keys_in", n_in)
            dedup = self._native_dedup()
            if dedup is not None:
                stat_add("ps.engine.dedup_keys_native", n_in)
                return dedup(parts)
            allk = np.concatenate(parts) if parts else \
                np.empty((0,), np.uint64)
            uniq = np.unique(allk)
            return uniq[uniq != 0]  # key 0 = reserved zero row

    def _build_host(self, uniq: np.ndarray,
                    mapper: Optional[embedding.PassKeyMapper] = None
                    ) -> tuple:
        # the pass-build bulk pull is one of the two big wire transfers
        # per pass (with the end-pass delta push) — surface its wall time
        # in the monitor so the pipelined PS wire path's effect shows up
        # beside the ps.wire.* byte counters (ps/service.py)
        snap = self._feed_cache_snap
        with self.timers("build_pull"), \
                trace.span("ps.engine.pull", keys=len(uniq)):
            t0 = time.monotonic()
            plan = None
            if snap is not None and len(snap.keys) and len(uniq):
                # HBM tier: pull only cache MISSES over the wire; the
                # snapshot-hit rows are filled from the device cache at
                # adoption (begin_pass, main thread)
                hit_mask = snap.lookup(uniq)
                miss = uniq[~hit_mask]
                if len(miss):
                    pulled = self.table.bulk_pull(miss)
                    miss_pos = np.flatnonzero(~hit_mask)
                    host_rows = {}
                    for f, v in pulled.items():
                        full = np.zeros((len(uniq),) + v.shape[1:], v.dtype)
                        full[miss_pos] = v
                        host_rows[f] = full
                else:
                    host_rows = self.cache.host_templates(len(uniq))
                plan = CachePlan(uniq[hit_mask], np.flatnonzero(hit_mask),
                                 snap, len(miss),
                                 miss if len(miss) else None)
                pulled_n = len(miss)
            else:
                host_rows = self.table.bulk_pull(uniq)
                pulled_n = len(uniq)
                if self.cache is not None:
                    stat_add("ps.cache.misses", float(len(uniq)))
                    if heat.ACTIVE is not None:
                        heat.ACTIVE.observe_cache(0, len(uniq))
            t1 = time.monotonic()
            intervals.record("pull", t0, t1)
            stat_add("ps.engine.build_pull_s", t1 - t0)
            stat_add("ps.engine.build_pull_rows", float(pulled_n))
        if mapper is None:
            mapper = embedding.PassKeyMapper(uniq)
        return mapper, len(uniq), host_rows, plan

    def _upload(self, host_rows) -> Dict[str, jnp.ndarray]:
        # The ws built here is the one contract every step path consumes
        # — fast's padded [S,L,B] gathers and mxu's sorted chunks index
        # the same [N]-row SoA (row 0 reserved zero), so path selection
        # never changes what begin_pass/end_pass upload or write back.
        #
        # ctr_double accessor: the host keeps f64 show/click; the device
        # trains in f32, so end_pass writes back host + (device delta) in
        # f64 — counters stay exact past f32's 2^24 integer range
        # (≙ DownpourCtrDoubleAccessor, ctr_double_accessor.h)
        if host_rows["show"].dtype == np.float64:
            self._pulled_stats = {f: host_rows[f].copy()
                                  for f in ("show", "click")}
        else:
            self._pulled_stats = None
        with self.timers("build_device"):
            t0 = time.monotonic()
            sharding = (self.topology.table_sharding()
                        if self.topology is not None else None)
            ws = embedding.build_working_set(
                host_rows, self.config.embedding_dim, sharding=sharding,
                buffers=self._ws_buffers)
            intervals.record("upload", t0, time.monotonic())
            if self._pulled_stats is not None:
                # exact per-pass counter accumulators (small magnitudes
                # stay exact in f32); merged into the f64 host stats at
                # end_pass
                ws["show_acc"] = jnp.zeros_like(ws["show"])
                ws["click_acc"] = jnp.zeros_like(ws["click"])
            return ws

    def _adopt(self, mapper, n: int, host_rows,
               plan: Optional[CachePlan]) -> Dict[str, jnp.ndarray]:
        """Main-thread working-set assembly: resolve the feed's cache plan
        against the live index, wire-pull any hit that was evicted since
        the snapshot, reconcile the f64 pulled-stats / delta-mode
        write-back base, upload the miss plane and gather the hit plane
        device-side."""
        if plan is None or self.cache is None:
            return self._upload(host_rows)
        with self.timers("cache_gather"):
            valid, slots = self.cache.resolve(plan.keys, plan.snap)
            n_valid = int(valid.sum())
            inv_keys = plan.keys[~valid]
            if len(inv_keys):
                # evicted (or invalidated) between snapshot and adoption —
                # an ordinary wire miss, just discovered late
                fresh = self.table.bulk_pull(inv_keys)
                inv_pos = plan.pos[~valid]
                for f, v in fresh.items():
                    if f in host_rows:
                        host_rows[f][inv_pos] = v
                stat_add("ps.engine.build_pull_rows", float(len(inv_keys)))
                stat_add("ps.cache.gather_fallback_rows",
                         float(len(inv_keys)))
            hit_pos = plan.pos[valid]
            hit_slots = np.asarray(slots[valid], np.int32)
            delta_seed = (getattr(self.table, "delta_mode", False)
                          and hasattr(self.table, "seed_snapshot"))
            if n_valid:
                if delta_seed:
                    # the write-back base for hit rows is the cache's host
                    # mirror (exactly what we last wrote back for them)
                    for f, v in self.cache.read_mirror(hit_slots).items():
                        if f in host_rows:
                            host_rows[f][hit_pos] = v
                elif host_rows["show"].dtype == np.float64:
                    # ctr_double: the f64 stats base comes from the mirror
                    for f, v in self.cache.read_mirror(
                            hit_slots, fields=("show", "click")).items():
                        host_rows[f][hit_pos] = v
            if delta_seed:
                # delta-mode remotes snapshot what they pull — only the
                # misses here.  Install the full assembled key set as the
                # write-back base, dropping the partial pull snapshots.
                consumed = [k for k in (plan.pulled_keys, inv_keys)
                            if k is not None and len(k)]
                self.table.seed_snapshot(mapper.sorted_keys, host_rows,
                                         consumed=consumed)
            ws = self._upload(host_rows)
            if n_valid:
                ws = self.cache.scatter_into(
                    ws, mapper(plan.keys[valid]), hit_slots)
            # rows assembled from post-write-back state at adoption time —
            # the stale-row refresh must not re-pull them
            self._cache_fresh_keys = np.union1d(
                plan.keys[valid], inv_keys) if len(inv_keys) \
                else plan.keys[valid]
            n_miss = plan.n_miss + len(inv_keys)
            stat_add("ps.cache.hits", float(n_valid))
            stat_add("ps.cache.misses", float(n_miss))
            stat_set("ps.cache.hit_rate",
                     n_valid / max(n_valid + n_miss, 1))
            if heat.ACTIVE is not None:
                # hot-coverage: share of this pass's pulled rows the
                # device cache served resident
                heat.ACTIVE.observe_cache(n_valid, n_miss)
            stat_add("ps.cache.bytes_saved",
                     float(n_valid * self.cache.row_bytes))
        return ws

    def _build(self, uniq: np.ndarray) -> tuple:
        mapper, n, host_rows, plan = self._build_host(uniq)
        with trace.span("ps.engine.upload_ws", rows=n):
            return mapper, n, self._adopt(mapper, n, host_rows, plan)

    def end_feed_pass(self, async_build: bool = False) -> None:
        """Dedup pass keys, pull host rows, build the device working set.

        async_build=True builds in a background thread for the NEXT pass
        while the current one is still training (≙ EndFeedPass handing the
        agent to the feedpass thread pool, box_wrapper.cc:152 +
        start_build_thread ps_gpu_wrapper.cc:907); adopt the result with
        begin_pass, which also refreshes rows the in-flight pass updates at
        its end_pass (the reference accepts that staleness — we do not).
        """
        assert self._feeding
        # pboxlint: disable-next=PB102 -- lifecycle flag, coordinator-only
        self._feeding = False
        uniq = self._dedup_agent_keys()
        flight.record("pass_feed_end", pass_id=self.pass_id + 1,
                      keys=len(uniq), asynchronous=async_build)
        if not async_build:
            assert self._build_thread is None and self._next is None, \
                "a preloaded pass is pending adoption (begin_pass) — " \
                "mixing it with a synchronous feed pass would discard data"
            self.mapper, self.num_keys, self.ws = self._build(uniq)
            return
        assert self._build_thread is None, "previous async build not adopted"
        # the pass's one mapper needs the dedup'd keys alone: made here, it
        # lets the prefetcher pack while the pull runs (peek_next_mapper),
        # and the build hands the SAME object to begin_pass, so the native
        # hash the pack builds is the one adoption looks keys up in
        mapper = embedding.PassKeyMapper(uniq)
        self._next_mapper = mapper

        # host-only work in the thread (dedup'd table pull — the slow DRAM/
        # SSD part); the device upload happens in begin_pass on the MAIN
        # thread: concurrent device dispatch from two python threads can
        # deadlock single-stream runtimes
        def run():
            try:
                self._next = self._build_host(uniq, mapper)
            except BaseException as e:  # re-raised in begin_pass, not lost
                self._build_error = e

        self._build_error = None
        # the handoff is coordinator-only: begin_pass joins before clearing
        # pboxlint: disable-next=PB102 -- coordinator-only thread handoff
        self._build_thread = threading.Thread(target=run, daemon=True)
        self._build_thread.start()

    def wait_feed_pass_done(self) -> None:
        """≙ BoxHelper::WaitFeedPassDone (box_wrapper.h:1156).  Raises if
        the background build failed — whichever of this or begin_pass runs
        first surfaces the error; a stale previous working set must never
        silently train in place of the failed pass."""
        if self._build_thread is not None:
            with trace.span("ps.engine.wait_build"):
                self._build_thread.join()
            self._build_thread = None
        err = getattr(self, "_build_error", None)
        if err is not None:
            self._build_error = None
            raise RuntimeError(
                "async working-set build failed (end_feed_pass "
                "background thread)") from err

    def feed_build_running(self) -> bool:
        """True while the async host build (the table pull) still runs."""
        t = self._build_thread
        return t is not None and t.is_alive()

    def peek_next_mapper(self) -> Optional[embedding.PassKeyMapper]:
        """The key mapper the NEXT begin_pass will adopt — available as
        soon as ``end_feed_pass(async_build=True)`` has deduplicated the
        keys, WITHOUT waiting on the host build or adopting the working
        set (the current mapper when no async build is pending).  The pass
        prefetcher packs pass N+1's feed against this on a background
        thread while the table pull runs and pass N still trains, then
        joins the pull (:meth:`wait_feed_pass_done`, which raises if it
        failed).  Key translation reads only the sorted key array, which
        neither the pull nor begin_pass's stale-row refresh touches (they
        fill working-set VALUES), so the early pack is bit-identical to
        packing after adoption."""
        if self._build_thread is not None or self._next is not None:
            return self._next_mapper
        return self.mapper

    # -- train pass ----------------------------------------------------------
    def begin_pass(self) -> None:
        with trace.span("ps.engine.begin_pass", pass_id=self.pass_id + 1):
            if self._build_thread is not None or self._next is not None:
                self.wait_feed_pass_done()  # raises if async build failed
                assert self._next is not None
                self.mapper, self.num_keys, host_rows, plan = self._next
                with trace.span("ps.engine.upload_ws", rows=self.num_keys):
                    self.ws = self._adopt(self.mapper, self.num_keys,
                                          host_rows, plan)
                self._next = self._next_mapper = None
                with trace.span("ps.engine.refresh_stale"):
                    self._refresh_stale_rows()
                self._cache_fresh_keys = None
            assert self.ws is not None, \
                "end_feed_pass must run before begin_pass"
            # promote the pending feed-time baseline: THIS pass's report
            # window (prefetch keeps N+1's pending window separate while
            # N's promoted one is still open)
            obs0 = getattr(self, "_feed_obs0", None)
            if obs0 is not None:
                self._pass_obs0 = obs0
                self._feed_obs0 = None
            self.pass_id += 1
            flight.record("pass_begin", pass_id=self.pass_id,
                          keys=self.num_keys)

    def _refresh_stale_rows(self) -> None:
        """An async-built working set pulled host rows while the previous
        pass was still training; rows that pass wrote at its end_pass are
        stale here.  Re-pull the intersection and overwrite."""
        if self._last_written is None or self.mapper is None \
                or self.num_keys == 0:
            return
        stale = np.intersect1d(self._last_written, self.mapper.sorted_keys,
                               assume_unique=True)
        fresh_keys = self._cache_fresh_keys
        if fresh_keys is not None and len(fresh_keys):
            # cache hits (and adoption-time fallback pulls) were assembled
            # AFTER the previous pass's write-back + fold-back — already
            # fresh, and re-pulling them would hand back the wire bytes
            # the cache just saved
            stale = np.setdiff1d(stale, fresh_keys, assume_unique=True)
        if not len(stale):
            return
        with self.timers("refresh_stale"):
            # remote tables: this pull retries through the exactly-once
            # protocol (service.py) — a dropped connection here no longer
            # aborts the pass adoption
            stat_add("ps.engine.stale_refresh_rows", float(len(stale)))
            fresh = self.table.bulk_pull(stale)
            if getattr(self, "_pulled_stats", None) is not None:
                pos = np.searchsorted(self.mapper.sorted_keys, stale)
                for f in ("show", "click"):
                    if f in fresh:
                        self._pulled_stats[f][pos] = fresh[f]
            if hasattr(self.table, "patch_snapshot"):
                # delta-mode remote tables: the refreshed values must also
                # replace the write-back base for these rows (service.py
                # RemoteTableAdapter.patch_snapshot)
                self.table.patch_snapshot(self.mapper.sorted_keys, stale,
                                          fresh)
            rows = jnp.asarray(self.mapper(stale))
            for f in self.ws:
                if f in fresh:
                    self.ws[f] = self.ws[f].at[rows].set(
                        jnp.asarray(fresh[f], self.ws[f].dtype))

    @trace.span("ps.engine.end_pass")
    def end_pass(self, need_save_delta: bool = False,
                 delta_path: str = "") -> None:
        """Write the trained working set back to the DRAM tier.

        Pass-level recovery contract: if the write-back raises (remote PS
        unreachable past the client's retry deadline), the engine state —
        ``ws``, ``mapper``, ``_pulled_stats`` — is left intact and a
        delta-mode RemoteTableAdapter restores its pull snapshot + pins
        the chunk rid-group, so calling ``end_pass`` again replays the
        SAME write-back exactly-once (already-applied chunks dedup
        server-side)."""
        assert self.ws is not None and self.mapper is not None
        if faults.ACTIVE is not None:
            # chaos SIGKILL-schedule site: a seeded kill here simulates the
            # trainer dying with a trained-but-unwritten pass — auto-resume
            # must re-drive the pass from the last checkpoint
            faults.on_lifecycle("end_pass")
        if embedding.is_quantized(self.ws):
            raise RuntimeError(
                "serving-frozen working set cannot write back (its embedx "
                "is an int16 grid, not the f32 store) — a frozen pass ends "
                "by discarding the device copy (engine.ws = None) or "
                "rebuilding the pass")
        with self.timers("dump_to_cpu"):
            with trace.span("ps.engine.dump_to_cpu"):
                soa = embedding.dump_working_set(self.ws, self.num_keys)
                soa["unseen_days"] = np.zeros((self.num_keys,), np.float32)
                if getattr(self, "_pulled_stats", None) is not None:
                    # f64 base + the exact per-pass delta accumulators —
                    # the absolute device copy may have rounded (f32 at
                    # 2^24+), the small-magnitude delta did not
                    for f in ("show", "click"):
                        soa[f] = self._pulled_stats[f] + \
                            soa[f + "_acc"].astype(np.float64)
                        del soa[f + "_acc"]
            try:
                t0 = time.monotonic()
                with trace.span("ps.engine.end_pass_write",
                                pass_id=self.pass_id, keys=self.num_keys):
                    self.table.bulk_write(self.mapper.sorted_keys, soa)
                intervals.record("write", t0, time.monotonic())
            except Exception:
                # keep _pulled_stats/ws/mapper: a re-driven end_pass must
                # rebuild the IDENTICAL soa (clearing the stats first used
                # to make the retry write absolute f32 values — divergent)
                stat_add("ps.engine.end_pass_write_failure")
                raise
            self._pulled_stats = None
            if self.cache is not None:
                # fold-back: the ONLY cache row mutation (PB503) — after
                # the table write succeeded, so a failed write-back replays
                # end_pass with the cache untouched (exactly-once), and a
                # checkpoint commit never sees cache-only state
                with self.timers("cache_fold"):
                    fold, casts = soa, None
                    pop = getattr(self.table, "pop_write_effect", None)
                    eff = pop() if pop is not None else None
                    if eff is not None:
                        # delta-mode remote: the server materialized
                        # base+delta, which can differ from the written
                        # soa in the last ulp — the cache must hold the
                        # SERVER's bits or a later hit diverges from the
                        # wire pull it replaces
                        fold = eff
                        casts = {f: eff[f] for f in eff
                                 if f != "unseen_days"}
                    elif soa["show"].dtype == np.float64:
                        # hit rows must replay the same f64→f32 cast a
                        # wire pull of the written row would
                        casts = {f: soa[f].astype(np.float32)
                                 for f in ("show", "click")}
                    self.cache.update_after_pass(
                        self.mapper.sorted_keys, fold, self.ws,
                        pass_id=self.pass_id, host_casts=casts)
        self.ws = None
        self._last_written = np.asarray(self.mapper.sorted_keys)
        # feed-gap attribution over THIS pass's window (begin_feed_pass →
        # write-back done), overlap-aware: surfaces in /statz, the
        # per-pass report, and the BENCH result JSON (ROADMAP item 2)
        obs0 = getattr(self, "_pass_obs0", None) or {}
        m0 = obs0.get("m0")
        if m0 is not None:
            rep = intervals.report(since=m0)
            self._pass_feed_report = rep
            stat_set("feed.device_busy_frac", rep["device_busy_frac"])
            stat_set("feed.feed_gap_ratio", rep["feed_gap_ratio"])
            # per-stage prefetch-hidden seconds: host feed work that ran
            # UNDER device busy — the pipelined engine's win in /statz
            for k in ("pull", "pack", "upload", "write"):
                # pboxlint: disable-next=PB204 -- closed kind set (intervals.KINDS)
                stat_set(f"feed.{k}_hidden_s", rep.get(f"{k}_hidden_s", 0.0))
        flight.record("pass_end", pass_id=self.pass_id,
                      keys=self.num_keys)
        if flags.get_flags("obs_pass_report"):
            print(self.pass_report(), flush=True)
        if need_save_delta and delta_path:
            self.save_delta(delta_path)

    def reset_feed_state(self) -> None:
        """Drop every in-flight feed/pass artifact so a checkpoint restore
        starts from a clean pass boundary (io/checkpoint.py resume, and
        fleet.train_passes' auto-resume loop after a simulated trainer
        death).  Joins a live async build first — its thread touches
        ``_next``/``_build_error`` and must not race the reset — then
        clears the working set, mapper, agent sink and the stale-row
        cursor (the restored table already reflects the last durable
        pass; replaying a stale ``_last_written`` would re-pull rows the
        rollback discarded)."""
        t = self._build_thread
        if t is not None:
            t.join(timeout=30)
        # crash-recovery teardown: the only writer thread joined above
        # pboxlint: disable-next=PB102 -- no concurrent builder remains
        self._build_thread = None
        self._build_error = None
        self._next = None
        self._next_mapper = None
        with self._agent_lock:
            self._agent_keys = []
        # pboxlint: disable-next=PB102 -- single-coordinator lifecycle flag
        self._feeding = False
        self._feed_obs0 = None
        self._pass_obs0 = None
        self.ws = None
        self.mapper = None
        self.num_keys = 0
        self._pulled_stats = None
        self._last_written = None
        self._feed_cache_snap = None
        self._cache_fresh_keys = None
        if self.cache is not None:
            # coherence point: a checkpoint restore / crash teardown may
            # roll the table back past rows the cache folded in — rebuild
            # cold (covers io/checkpoint.resume, PassPrefetcher.abort and
            # fleet.train_passes' auto-resume loop)
            self.cache.invalidate("reset")

    def freeze_for_serving(self, scale: float = 1.0 / 32767.0) -> None:
        """Re-encode the live working set's embedx as int16 for pull-only
        serving (≙ loading a quant-feature table + EmbedxQuantOp dequant,
        box_wrapper.cu:37 / pull_embedx_scale box_wrapper.h:655): embedx
        pulls read half the bytes, the table holds half the HBM.  Training
        on a frozen set raises — re-run the pass lifecycle to train."""
        assert self.ws is not None, "no live working set to freeze"
        qb = self.config.quant_bits or 16
        self.ws = embedding.quantize_working_set(self.ws, qb, scale)
        if self.cache is not None:
            # a frozen pass never writes back — don't let its rows serve
            # as a later pass's write base
            self.cache.invalidate("freeze")

    # -- persistence ---------------------------------------------------------
    def _save(self, path: str, mode: str) -> int:
        rows = self.table.save(path, mode=mode)
        flight.record("checkpoint_save", mode=mode, path=path, rows=rows)
        return rows

    def save_base(self, path: str) -> int:
        return self._save(path, "base")

    def save_delta(self, path: str) -> int:
        return self._save(path, "delta")

    def save_checkpoint(self, path: str) -> int:
        return self._save(path, "all")

    def load(self, path: str) -> int:
        rows = self.table.load(path)
        flight.record("checkpoint_load", path=path, rows=rows)
        if self.cache is not None:
            self.cache.invalidate("load")
        return rows

    def shrink(self) -> int:
        removed = self.table.shrink()
        if self.cache is not None:
            # shrink evicted dead table rows — cached copies of them must
            # not resurrect through a later fold-back's write base
            self.cache.invalidate("shrink")
        return removed

    # -- convenience ---------------------------------------------------------
    def attach_dataset(self, dataset) -> None:
        """Register this engine as the dataset's feasign consumer
        (≙ PadBoxSlotDataset holding the BoxWrapper agent)."""
        dataset.register_key_consumer(self.add_keys)

    def print_sync_timers(self) -> str:
        return self.timers.report()

    def pass_report(self) -> str:
        """PrintSyncTimer-style per-pass wall-time table (≙ PrintSyncTimer
        box_wrapper.h:795): the phase seconds of THIS pass (deltas since
        begin_feed_pass), plus the pass's wire bytes, pipeline pressure
        and injected-fault counts — the at-a-glance answer to "was this
        pass pull-bound, train-bound or write-bound?".  Printed at every
        end_pass under ``FLAGS_obs_pass_report``."""
        obs0 = getattr(self, "_pass_obs0", None) or {}
        stats0 = obs0.get("stats0") or {}
        timers0 = obs0.get("timers0") or {}
        cur = {**stat_snapshot("ps."), **stat_snapshot("ckpt.")}

        def delta(key: str) -> float:
            return cur.get(key, 0.0) - stats0.get(key, 0.0)

        lines = [f"---- PrintSyncTimer pass {self.pass_id} "
                 f"day {self.day_id or '-'} ----",
                 f"  {'phase':<20} {'seconds':>10} {'count':>7}"]
        for name, secs, count in self.timers.rows():
            s0, c0 = timers0.get(name, (0.0, 0))
            if count - c0 == 0 and secs - s0 < 1e-9:
                continue            # phase did not run this pass
            lines.append(f"  {name:<20} {secs - s0:>10.3f} "
                         f"{count - c0:>7d}")
        tx = {k[len("ps.wire."):-len(".tx_bytes")]: delta(k)
              for k in cur if k.startswith("ps.wire.")
              and k.endswith(".tx_bytes") and delta(k) > 0}
        if tx:
            per_verb = " ".join(f"{v}={int(b)}" for v, b in sorted(tx.items()))
            lines.append(f"  wire tx_bytes: total={int(sum(tx.values()))} "
                         f"({per_verb})")
        lines.append(
            f"  inflight_hwm={int(cur.get('ps.client.inflight_hwm', 0))} "
            f"pipeline_stall={delta('ps.client.pipeline_stall_s'):.3f}s "
            f"retries={int(delta('ps.client.retry'))} "
            f"dedup_hits={int(delta('ps.server.dedup_hit'))}")
        ch, cm = delta("ps.cache.hits"), delta("ps.cache.misses")
        if ch or cm:
            # HBM-tier effectiveness for THIS pass: wire rows the device
            # cache kept off the network, vs rows still pulled
            lines.append(
                f"  cache: hits={int(ch)} misses={int(cm)} "
                f"hit_rate={ch / max(ch + cm, 1.0):.2f} "
                f"resident={int(cur.get('ps.cache.resident_rows', 0))} "
                f"evictions={int(delta('ps.cache.evictions'))} "
                f"bytes_saved={int(delta('ps.cache.bytes_saved'))}")
        pool_tasks = delta("ps.pool.table.tasks")
        if pool_tasks:
            # shard-pool pressure for THIS pass: busy seconds across
            # workers, plus the process-lifetime queue/active high-water
            # marks — the at-a-glance answer to "is the table apply
            # pool-parallel or queueing on a hot shard?"
            lines.append(
                f"  pool table: tasks={int(pool_tasks)} "
                f"busy={delta('ps.pool.table.busy_s'):.3f}s "
                f"threads={int(cur.get('ps.pool.table.threads', 1))} "
                f"queue_hwm={int(cur.get('ps.pool.table.queue_depth_hwm', 0))} "
                f"active_hwm={int(cur.get('ps.pool.table.active_hwm', 0))} "
                f"util_p95={cur.get('ps.pool.table.utilization.p95', 0.0):.2f}")
        faults_n = sum(delta(k) for k in cur if k.startswith("ps.fault."))
        if faults_n:
            lines.append(f"  injected_faults={int(faults_n)}")
        if delta("ckpt.save_s.count") > 0 or delta("ckpt.restore_s.count"):
            # this pass paid checkpoint cost (generation-chained save at
            # the pass boundary, or a crash-recovery restore mid-window)
            lines.append(
                f"  ckpt: saves={int(delta('ckpt.save_s.count'))} "
                f"save_s={delta('ckpt.save_s.sum'):.3f} "
                f"delta_rows={int(delta('ckpt.delta_rows'))} "
                f"restores={int(delta('ckpt.restore_s.count'))} "
                f"restore_s={delta('ckpt.restore_s.sum'):.3f} "
                f"generation={int(cur.get('ckpt.generation', -1))}")
        q = stat_snapshot("quality.")
        if q.get("quality.passes"):
            # training-quality trajectory (metrics/quality.py): the
            # latest pass's AUC next to its windowed value and the drift
            # monitors the SLO watchdog reads
            lines.append(
                f"  quality: auc={q.get('quality.auc', 0.0):.4f} "
                f"auc_window={q.get('quality.auc_window', 0.0):.4f} "
                f"auc_drop={q.get('quality.auc_drop', 0.0):.4f} "
                f"calib_drift={q.get('quality.calibration_drift', 0.0):.4f} "
                f"psi={q.get('quality.psi.prediction', 0.0):.4f}")
        rep = getattr(self, "_pass_feed_report", None)
        if rep:
            # interval-accounted utilization (utils/intervals.py): how
            # much of the pass wall the device actually had work, and
            # how much host feed time hid behind it
            lines.append(
                f"  feed gap: wall={rep['wall_s']:.3f}s "
                f"device_busy={rep['device_busy_s']:.3f}s "
                f"device_busy_frac={rep['device_busy_frac']:.2f} "
                f"feed_gap_ratio={rep['feed_gap_ratio']:.2f}")
            lines.append(
                f"  host busy: pull={rep['pull_busy_s']:.3f}s "
                f"pack={rep['pack_busy_s']:.3f}s "
                f"upload={rep['upload_busy_s']:.3f}s "
                f"write={rep['write_busy_s']:.3f}s "
                f"overlapped_with_device={rep['overlap_s']:.3f}s")
            hidden = {k: rep.get(f"{k}_hidden_s", 0.0)
                      for k in ("pull", "pack", "upload", "write")}
            if any(v > 1e-9 for v in hidden.values()):
                # per-stage feed work hidden behind device busy — the
                # prefetch pipeline's visible effect (data/prefetch.py)
                lines.append(
                    "  prefetch hidden: " + " ".join(
                        f"{k}={v:.3f}s" for k, v in hidden.items()))
        return "\n".join(lines)
