"""Pass-pipelined prefetcher — train pass N while pass N+1 feeds.

≙ the reference's pass pipeline: PreLoadIntoMemory reads the next pass's
files while the current one trains (box_wrapper.h:1141), EndFeedPass hands
the key agent to the feedpass thread pool (box_wrapper.cc:152), the
pre-build thread pulls + builds the next working set under training
(ps_gpu_wrapper.cc:907-955), and PackBatchTask packs batches asynchronously
while the GPU runs (boxps_worker.cc:1259).  BENCH_r03 measured exactly the
gap this hides: ``device_step=473090`` vs ``end_to_end=22934`` ex/s — the
device idles ~95% of the wall waiting on serial pull+pack.

``PassPrefetcher`` drives the whole next-pass feed chain on ONE background
worker thread while the trainer runs the current pass:

    worker (pass N+1):  begin_feed_pass -> load_fn() [reader threads feed
                        keys] -> end_feed_pass(async_build=True) [key
                        dedup + mapper here; host bulk_pull starts on the
                        engine's build thread] -> peek_next_mapper ->
                        trainer.pack_pass_host [fans across the pack
                        WorkPool, beside the pull] -> wait_feed_pass_done
                        [joins the pull] -> buffer.put
    main   (pass N+1):  next_pass(): buffer.get -> engine.begin_pass
                        [adopt + ws upload + stale-row refresh] ->
                        trainer.finish_pass_feed [H2D + plans] -> train

Division of labour is deliberate:

* Host-only work (file read, key dedup, table pull, numpy pack) runs on
  background threads — it releases the GIL and the device never sees it.
* EVERY device dispatch (working-set upload, feed H2D, plan builds) stays
  on the main thread — concurrent device dispatch from two python threads
  can deadlock single-stream runtimes (ps/pass_manager.py's async_build
  keeps the same boundary).

Bounded double buffer: the hand-off channel holds ONE packed pass, so at
most two passes are resident host-side (the training pass's device feed +
the prefetched pass's host planes) — memory is bounded at ~2 packed feeds
regardless of how many specs are queued.  The worker also gates each
spec on the PREVIOUS pass's adoption, because the engine holds a single
``_next`` working-set slot (and a single pending feed-obs window).

Pack beside pull: the engine builds the pass's key mapper from the
deduplicated keys as soon as the dedup returns, so the pack (which needs
only the mapper) runs while the build thread still pulls the table rows,
and the wait is max(pull, pack) instead of their sum.  The worker joins
the pull after the pack: a pass in the buffer has both its planes packed
and its host rows pulled, and a failed pull fails that pass at
:meth:`PassPrefetcher.next_pass`.  ``data.prefetch.pack_beside_pull``
counts the passes whose pack began while the pull still ran.

Bit-identity: the worker packs against ``engine.peek_next_mapper()`` —
the mapper object the upcoming ``begin_pass`` will adopt.  Key
translation reads only the mapper's sorted key array, which neither the
pull nor adoption's stale-row refresh touches (they fill working-set
VALUES), so packing before the pull ends produces byte-identical planes
to packing after adoption — pinned by tests/test_pass_pipeline.py,
including under fault injection.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from paddlebox_tpu import flags
from paddlebox_tpu.utils import flight, lockdep, trace
from paddlebox_tpu.utils.channel import Channel, ChannelClosed
from paddlebox_tpu.utils.monitor import stat_add

flags.define_flag(
    "pass_prefetch", True,
    "pipeline the pass feed: while pass N trains, pass N+1's load/key-"
    "dedup/table-pull/pack run on background threads (bounded double "
    "buffer, ~2 packed passes resident).  Device dispatch stays on the "
    "main thread; results are bit-identical to the serial pass loop")


class _Spec:
    __slots__ = ("load_fn", "tag", "keep_host", "date")

    def __init__(self, load_fn, tag, keep_host, date):
        self.load_fn = load_fn
        self.tag = tag
        self.keep_host = keep_host
        self.date = date


class PassPrefetcher:
    """Drive pass N+1's feed chain in the background while N trains.

    Usage (fleet.train_passes and bench.py's pass-cycle phase are the
    in-tree drivers)::

        pf = PassPrefetcher(engine, trainer)
        for filelist in passes:
            pf.submit(lambda fl=filelist: load(fl))   # returns the dataset
        for _ in passes:
            feed = pf.next_pass()     # engine.begin_pass done, feed ready
            trainer.train_pass(feed)
            engine.end_pass()
        pf.close()

    ``load_fn`` runs on the worker thread INSIDE an open feed pass: it
    must load the pass's data so that the engine's key sink sees every
    feasign (e.g. ``SlotDataset.load_into_memory`` with the engine
    attached), then return the loaded dataset for the pack.

    Device-cache interaction (ps/device_cache.py): ``begin_feed_pass`` —
    which runs HERE, on the worker thread — publishes the cache's
    immutable index snapshot, and the async build's miss-only pull
    intersects against that frozen view while pass N trains and folds
    back on the main thread (copy-on-write index, no torn reads).  The
    authoritative hit resolution and the device-side gather happen at
    adoption on the main thread, so a row evicted mid-overlap simply
    falls back to a wire pull.  The day-boundary drain above also orders
    ``set_date``'s cache invalidation strictly after the old day's last
    fold-back, and :meth:`abort`'s ``reset_feed_state`` rebuilds the
    cache cold.
    """

    def __init__(self, engine, trainer, keep_host: bool = False):
        self.engine = engine
        self.trainer = trainer
        self._keep_host = keep_host
        self._specs: Channel = Channel(capacity=1024)
        self._ready: Channel = Channel(capacity=1)   # the double buffer
        # pipeline position counters (one condition guards all three):
        # worker spec index vs how many passes the consumer has adopted
        # (begin_pass done) and ended (write-back done)
        self._cond = lockdep.condition("data.prefetch.PassPrefetcher._cond")
        self._adopted_n = 0
        self._ended_n = 0
        self._closing = False
        self._failed: Optional[BaseException] = None
        # recurring worker with a managed lifecycle (close() joins it) —
        # exactly the shape PB405 wants, so no suppression needed
        self._worker = threading.Thread(
            target=self._run, name="pbox-prefetch", daemon=True)
        self._worker.start()

    # -- producer side -------------------------------------------------------
    def submit(self, load_fn: Callable[[], object],
               tag: Optional[str] = None,
               keep_host: Optional[bool] = None,
               date: Optional[str] = None) -> None:
        """Queue one pass spec; the worker drives its feed chain as soon
        as the previous pass is adopted.

        date: run engine.set_date(date) before this pass's feed.  A date
        CHANGE runs end_day (whole-table decay), so the worker first
        drains the pipeline — it waits until every prior pass has ENDED
        (write-back done), which requires the consumer to end passes via
        :meth:`end_pass` (engine.end_pass alone never wakes the gate)."""
        keep = self._keep_host if keep_host is None else keep_host
        self._specs.put(_Spec(load_fn, tag, keep, date))

    def _wait(self, counter: str, need: int) -> None:
        with self._cond:
            while getattr(self, counter) < need and not self._closing:
                self._cond.wait(timeout=1.0)

    def _run(self) -> None:
        idx = 0
        while True:
            try:
                spec = self._specs.get()
            except ChannelClosed:
                return
            # the engine holds ONE pending working set (_next) and ONE
            # pending obs window — wait until the previous pass adopted
            # both.  Adoption happens at the START of its training, so
            # this whole chain still overlaps that training.
            with trace.span("data.prefetch.gate_wait"):
                self._wait("_adopted_n", idx)
                if spec.date is not None \
                        and spec.date != self.engine.day_id:
                    # day boundary: end_day decays the WHOLE table, so it
                    # must order strictly between the old day's last
                    # write-back and the new day's first pull — drain the
                    # pipeline
                    self._wait("_ended_n", idx)
            if self._closing:
                return
            if spec.date is not None:
                self.engine.set_date(spec.date)   # a new day: end_day here
            idx += 1
            try:
                t0 = time.monotonic()
                with trace.span("data.prefetch.build", tag=spec.tag or ""):
                    self.engine.begin_feed_pass()
                    dataset = spec.load_fn()
                    self.engine.end_feed_pass(async_build=True)
                    # the mapper begin_pass will adopt exists once the
                    # dedup returns: pack against it while the build
                    # thread still pulls the host rows
                    mapper = self.engine.peek_next_mapper()
                    if self.engine.feed_build_running():
                        stat_add("data.prefetch.pack_beside_pull")
                    arrays = self.trainer.pack_pass_host(dataset,
                                                         mapper=mapper)
                    # a pass is handed over packed AND pulled; a failed
                    # pull raises here and fails this pass
                    self.engine.wait_feed_pass_done()
                stat_add("data.prefetch.passes")
                flight.record("prefetch_pass_ready", tag=spec.tag,
                              records=arrays.num_real,
                              build_s=round(time.monotonic() - t0, 3))
                if not self._ready.put((arrays, dataset, spec, None)):
                    return            # closed mid-shutdown: drop and exit
            except BaseException as e:
                # surfaced at next_pass — a failed prefetch must fail THAT
                # pass, never silently train a stale working set
                self._failed = e
                flight.record("prefetch_pass_failed", tag=spec.tag,
                              error=type(e).__name__)
                self._ready.put((None, None, spec, e))
                return

    # -- consumer side -------------------------------------------------------
    def next_pass(self):
        """Block until the next prefetched pass is packed, adopt it
        (engine.begin_pass on THIS thread: ws upload + stale-row refresh)
        and finish the feed (H2D + plans).  Returns the PackedPassFeed.

        The blocked time here is the pipeline's residual — feed seconds
        the training pass could NOT hide (``data.prefetch.wait_s``)."""
        with trace.span("data.prefetch.wait"):
            arrays, dataset, spec, err = self._ready.get()
        if err is not None:
            raise RuntimeError(
                f"pass prefetch failed (spec {spec.tag or '?'})") from err
        self.engine.begin_pass()
        feed = self.trainer.finish_pass_feed(arrays,
                                             keep_host=spec.keep_host)
        with self._cond:          # frees the worker to open the next feed
            lockdep.guards(self, "_adopted_n")
            self._adopted_n += 1
            self._cond.notify_all()
        self._last_dataset = dataset
        return feed

    def end_pass(self, need_save_delta: bool = False,
                 delta_path: str = "") -> None:
        """engine.end_pass + wake the worker's day-boundary gate.  Drivers
        that submit dated specs MUST end passes through here."""
        self.engine.end_pass(need_save_delta, delta_path)
        with self._cond:
            self._ended_n += 1
            self._cond.notify_all()

    def close(self) -> None:
        """Stop the worker and join it.  Safe after errors and mid-queue:
        unprocessed specs are dropped (their passes never began)."""
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        self._specs.close()
        self._ready.close()
        self._worker.join(timeout=30.0)

    def abort(self) -> None:
        """Crash-recovery teardown (fleet.train_passes' auto-resume tier):
        stop + join the worker like :meth:`close`, then clear the ENGINE's
        in-flight feed state — the worker may have died holding an open
        feed window or an unadopted async build, and the checkpoint
        restore that follows must start from a clean pass boundary
        (pass_manager.BoxPSEngine.reset_feed_state)."""
        self.close()
        if hasattr(self.engine, "reset_feed_state"):
            self.engine.reset_feed_state()

    def __enter__(self) -> "PassPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
