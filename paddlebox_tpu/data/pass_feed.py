"""Pass-scoped device-resident batch feed — whole-pass pack, once.

≙ the reference's pass-scope GPU data path: SlotPaddleBoxDataFeed packs the
whole pass on device at feed time (data_feed.h:2036, MiniBatchGpuPack
data_feed.h:519, FillSlotValueOffsetPadBoxKernel / CopyForTensorPadBoxKernel
data_feed.cu:1210-1318) and translates keys once per pass during the build
(DedupKeysAndFillIdx, box_wrapper_impl.h:129) — so the train loop touches no
per-batch host work.

TPU-first shape of the same idea:

* HOST, once per pass (vectorized numpy over a slot's records at once,
  read from the parsed blocks where they lie): ragged slot values ->
  translated pass-row ids (one lookup a slot x record range, never one a
  batch) -> padded [S, N*B, L] planes kept from pass to pass (PlaneStore).
* DEVICE, once per pass: one relayout jit to the step's [N, S, L, B] layout
  plus (for the mxu path) the per-batch sort plans (ops/sorted_spmm
  build_plan mapped over batches) — the TPU equivalent of the reference
  keeping the packed pass + dedup index resident on the GPU.
* TRAIN LOOP: the jitted step takes a batch index and dynamic-slices the
  resident arrays; per-batch host work is one integer dispatch.

The per-batch host path (`data/batch_pack.py`) remains for streaming
datasets that do not fit pass-resident.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from paddlebox_tpu.config import DataFeedConfig, SlotConfig
from paddlebox_tpu.data.batch_pack import BatchPacker
from paddlebox_tpu.data.slot_record import (SlotRecordBlock, aligned_slab,
                                            size_class)
from paddlebox_tpu.utils import intervals, trace, workpool
from paddlebox_tpu.utils.monitor import stat_add, stat_observe


@dataclasses.dataclass
class HostPassArrays:
    """Whole pass, packed host-side (numpy), batch-major."""

    indices: np.ndarray    # [S, N*B, L] int32 pass-local rows (0 = padding)
    lengths: np.ndarray    # [S, N*B] int32
    dense: np.ndarray      # [N*B, D] float32
    labels: np.ndarray     # [N*B] or [N*B, T] float32
    valid: np.ndarray      # [N*B] bool
    n_batches: int
    batch_size: int
    num_real: int          # real record count (pass total)
    ins_ids: Optional[list] = None
    # prebatched (pv-aligned) packs: per-batch real counts + prefix sums
    # into the real-record order (dump/ins_ids addressing); None = records
    # are densely packed and batch i holds rows [i*B, i*B + real_i)
    batch_real: Optional[np.ndarray] = None   # [N] int64
    batch_base: Optional[np.ndarray] = None   # [N] int64
    rank_offset: Optional[np.ndarray] = None  # [N*B, 1+2*max_rank] int32
    ads_offset: Optional[np.ndarray] = None   # [N, B+1] int32 pv offsets
    # a tied head's keys as working-set rows, the same in every batch of
    # the pass (stacked so that a batch slices it as it slices the rest)
    head_rows: Optional[np.ndarray] = None    # [N, V] int32
    # InputTable-resolved aux index planes {name: [N*B, cap] int32}
    aux: Optional[Dict[str, np.ndarray]] = None
    uid: Optional[np.ndarray] = None    # [N*B] uint64 (uid_slot, HOST-side:
    #   uids never ship to device — wuauc accumulates on host)
    # the PlaneStore buffers the planes above are views of: whoever hands
    # them back (PlaneStore.give_back) says that nothing reads a plane any more
    storage: Optional[List[np.ndarray]] = None

    def extra_planes(self) -> Dict[str, np.ndarray]:
        """Every optional per-record plane (rank_offset + aux index
        planes) — single source for upload/relayout/sharding plumbing."""
        out = {}
        if self.rank_offset is not None:
            out["rank_offset"] = self.rank_offset
        if self.aux:
            out.update(self.aux)
        return out

    def real_range(self, i: int):
        """(plane_row_lo, real_count, real_order_base) of batch i."""
        if self.batch_real is not None:
            return (i * self.batch_size, int(self.batch_real[i]),
                    int(self.batch_base[i]))
        lo = i * self.batch_size
        return lo, max(0, min(self.batch_size, self.num_real - lo)), lo


class PlaneStore:
    """Memory of a packed pass's host planes, kept from pass to pass (the
    pack's counterpart of the reader's ``BlockStore``).  ``take`` answers
    with an UNINITIALISED array of the asked shape, a view of the smallest
    buffer handed back that can hold it, else of a new one (a size class
    of ``slot_record.size_class``, so passes of nearly one size find each
    other's buffers); the pack writes every byte of it.  ``give_back``
    takes the buffers of a pass whose planes nobody reads any more; the
    store keeps those of the last two such passes (one trains while the
    next is packed) and lets go of older ones.  It also keeps each pack
    thread's scratch (gathered values, end offsets, lengths), so a task
    allocates nothing of its group's size but the translated rows.
    Thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sets: List[List[np.ndarray]] = []     # oldest first, <= 2
        self._scratch = threading.local()

    @property
    def free_bytes(self) -> int:
        with self._lock:
            return sum(buf.nbytes for bufs in self._sets for buf in bufs)

    def take(self, shape, dtype, held: List[np.ndarray]) -> np.ndarray:
        """An uninitialised ``shape`` / ``dtype`` array; its buffer is
        appended to ``held``, the list to give back."""
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        buf = None
        with self._lock:
            fit = min(((buf.nbytes, si, bi)
                       for si, bufs in enumerate(self._sets)
                       for bi, buf in enumerate(bufs)
                       if buf.nbytes >= nbytes), default=None)
            if fit is not None:
                buf = self._sets[fit[1]].pop(fit[2])
        if buf is None:
            stat_add("data.pack.plane_bytes_fresh", nbytes)
            buf = aligned_slab(size_class(nbytes))
        else:
            stat_add("data.pack.plane_bytes_reused", nbytes)
        held.append(buf)
        return buf[:nbytes].view(dtype).reshape(shape)

    def give_back(self, buffers: Sequence[np.ndarray]) -> None:
        if not buffers:
            return
        with self._lock:
            self._sets = self._sets[-1:] + [list(buffers)]

    def scratch(self, what: str, n: int, dtype) -> np.ndarray:
        """``n`` uninitialised elements that stay the calling thread's
        until its next call for the same ``what`` (and dtype)."""
        dtype = np.dtype(dtype)
        kept = self._scratch.__dict__
        buf = kept.get((what, dtype))
        if buf is None or len(buf) < n:
            buf = kept[what, dtype] = np.empty(
                size_class(n * dtype.itemsize) // dtype.itemsize, dtype)
        return buf[:n]


def _record_ranges(n: int, threads: int, slots: int) -> List[tuple]:
    """Split [0, n) into contiguous record ranges for the pack fan-out.
    The tasks are (slot x range): the slots alone feed the pool where
    there are enough of them, and ranges make up about two tasks a thread
    (slot-length skew) where there are not (a sequence model's one slot,
    the few dense and label slots of the second wave);
    a floor keeps tiny passes from paying per-task overhead.  Few large
    tasks, because a task is a handful of calls that release the
    interpreter lock (one gather, one translation, one landing) and the
    Python between them does not overlap.  Pure partitioning — workers
    write disjoint plane rows, so any split is bit-identical."""
    if n == 0:
        return []
    if threads <= 1:
        return [(0, n)]
    chunks = min(-(-threads * 2 // max(1, slots)), max(1, n // 4096))
    bounds = np.linspace(0, n, chunks + 1).astype(np.int64)
    return [(int(bounds[i]), int(bounds[i + 1]))
            for i in range(len(bounds) - 1) if bounds[i + 1] > bounds[i]]


def _gather_ragged(segments, slot_of, store: PlaneStore):
    """One slot's records over a group of block segments ``(block, lo,
    hi)`` as ragged ``(values, lengths)``.  The values are a view of the
    block where the group lies in one, else joined by ONE
    ``np.concatenate`` (a copy a segment from Python costs several times
    more once pack threads contend for the interpreter lock); values and
    lengths lie in the calling thread's scratch, valid until its next
    gather."""
    vals, ends, first, before = [], [], [], []
    n = 0
    for block, lo, hi in segments:
        values, offsets = slot_of(block)
        vals.append(values[int(offsets[lo]):int(offsets[hi])])
        ends.append(offsets[lo + 1:hi + 1])
        first.append(n)                     # the segment's first record,
        before.append(int(offsets[lo]))     # and the offset it starts at
        n += hi - lo
    if len(vals) == 1:
        v = vals[0]
    else:
        v = store.scratch("values", sum(map(len, vals)), vals[0].dtype)
        np.concatenate(vals, out=v)
    # every record's end offset in its own block; a length is the
    # difference of two, but at a segment's first record
    end = np.concatenate(ends, out=store.scratch("ends", n, np.int64))
    lens = store.scratch("lengths", n, np.int64)
    np.subtract(end[1:], end[:-1], out=lens[1:])
    lens[first] = end[first] - np.asarray(before, np.int64)
    return v, lens


def _land_ragged(plane: np.ndarray, rows, col: int, width: int, cap: int,
                 values: np.ndarray, lens: np.ndarray):
    """Write the ragged records ``(values, lens)`` into
    ``plane[rows, col:col + width]``: a record's values from position 0,
    clipped at ``cap``, zero beyond them.  ``rows`` is a slice or an index
    array of the plane's rows, one a record.  Where every record of the
    group holds the same number of values that is one strided copy;
    otherwise a scatter over the values present, whose temporaries grow
    with them and not with ``n x width``.  Returns (lengths clipped at
    cap: a scalar on the strided landing, strided?)."""
    n, k = len(lens), int(lens[0])      # a group holds a record at least
    if int(lens.min()) == k == int(lens.max()):
        kk = min(k, cap)
        if kk == 1:     # one column: numpy walks an [n, 1] block row by row
            plane[rows, col] = values[::k]
        elif kk:
            plane[rows, col:col + kk] = values.reshape(n, k)[:, :kk]
        if kk < width:
            plane[rows, col + kk:col + width] = 0
        return kk, True
    plane[rows, col:col + width] = 0
    rec = np.repeat(np.arange(n), lens)
    at = np.arange(len(values)) - np.repeat(np.cumsum(lens) - lens, lens)
    if int(lens.max()) > cap:
        keep = at < cap
        rec, at, values = rec[keep], at[keep], values[keep]
        lens = np.minimum(lens, cap)
    row = rec + rows.start if isinstance(rows, slice) else rows[rec]
    plane[row, col + at] = values
    return lens, False


def route_keys(block: SlotRecordBlock) -> np.ndarray:
    """Per-record shuffle route key for the fleet's global shuffle-by-key
    (≙ the reference's shuffle_by_uid / global_shuffle key extraction):
    the FIRST feasign of the record's first non-empty uint64 slot, slots
    visited in sorted-name order.  Both orders are properties of the data
    alone — independent of reader thread, file split, or fleet size — so
    every fleet width routes a given record identically.  Records with no
    sparse key at all route as key 0 (all land on one slice; degenerate
    but still deterministic)."""
    keys = np.zeros(block.n, dtype=np.uint64)
    found = np.zeros(block.n, dtype=bool)
    for name in sorted(block.uint64_slots):
        vals, offs = block.uint64_slots[name]
        has = offs[1:] > offs[:-1]
        take = has & ~found
        if take.any():
            keys[take] = vals[offs[:-1][take]]
            found |= has
        if found.all():
            break
    return keys


def pack_pass(blocks: Sequence[SlotRecordBlock], feed_config: DataFeedConfig,
              batch_size: int, label_slot="label",
              key_mapper=None, prebatched: bool = False,
              batch_counts: Optional[Sequence[int]] = None,
              pack_threads: Optional[int] = None,
              on_plane: Optional[Callable[[str, np.ndarray], None]] = None,
              seq_key_slot: Optional[str] = None,
              head_keys: Optional[np.ndarray] = None,
              planes: Optional[PlaneStore] = None
              ) -> HostPassArrays:
    """Vectorized whole-pass pack, straight from the parsed blocks into
    the planes: a block's records are read where they lie (no merged copy
    of the pass) and land in the plane rows they own, one key translation
    a (slot x record range).

    prebatched: each input block IS one batch (≤ batch_size records, e.g.
    pv-aligned cuts from dataset.batches) and lands at its own batch slot,
    short batches padded — ≙ PadBoxSlotDataset's whole-pv batches feeding
    SlotPaddleBoxDataFeed.  batch_counts: same semantics but the cuts are
    given as per-batch record counts over the CONCATENATED block order
    (dataset.batch_bounds) — no per-batch block copies needed.  Otherwise
    the blocks' records are sliced densely every batch_size records.

    pack_threads: fan the per-slot/per-record-range gather+translate+land
    work across the shared pack WorkPool (None = FLAGS_pass_pack_threads;
    an explicit int uses a private pool of that size).  Every worker
    writes a DISJOINT row range of the SoA planes, so the result is
    bit-identical at any thread count (≙ the reference's per-device
    PackBatchTask threads, boxps_worker.cc:1259).

    on_plane: optional callable invoked on THIS thread as each finished
    SoA plane becomes final — upload_pass's per-plane H2D overlap hook
    (device dispatch stays on the pack coordinator thread).

    seq_key_slot: name of a sparse slot — adds the extras plane
    ``seq_keys`` [N*B, slot capacity] int32 of the slot's RAW keys (a
    sequence model's next-token targets are vocabulary ids; ``indices``
    holds working-set rows, which change every pass).  Keys must fit int32.

    head_keys: the keys whose rows are a model's tied head
    (``model.head_keys()``) — adds the per-batch plane ``head_rows``
    [N, V] int32, their working-set rows by ``key_mapper`` (0 for a key
    the pass does not hold), the same row of the plane for every batch.

    planes: the store the planes are taken from (and whose buffers the
    result names in ``storage``, for whoever may hand them back); None
    packs into memory of its own.  A taken plane is uninitialised, so the
    pack writes every byte: records' rows in the fan-out, padding rows
    ahead of it.
    """
    t_pack = time.perf_counter()
    m_pack = time.monotonic()
    packer = BatchPacker(feed_config, batch_size, label_slot)
    store = planes if planes is not None else PlaneStore()
    own_pool = None
    if pack_threads is None:
        pool = workpool.pack_pool()
    else:
        own_pool = pool = workpool.WorkPool(max(1, int(pack_threads)),
                                            kind="pack")
    if prebatched and batch_counts is None:
        batch_counts = [b.n for b in blocks]
    blocks = [b for b in blocks if b.n > 0]
    # record base of every block over the joined order, and the total
    bases = np.concatenate([[0], np.cumsum([b.n for b in blocks])]
                           ).astype(np.int64)
    n = int(bases[-1])
    head = blocks[0] if blocks else SlotRecordBlock(n=0)
    if batch_counts is not None:
        counts = [int(c) for c in batch_counts]
        if sum(counts) != n:
            raise ValueError(
                f"batch_counts sum {sum(counts)} != {n} records")
    else:
        counts = None
    if ((feed_config.rank_offset or feed_config.ads_offset)
            and counts is None):
        # the plane builder treats each batch slice as whole page views; a
        # pv split across dense cuts would silently attend over fragment
        # peers — every entry point inherits this guard, not just the
        # trainer (≙ GetRankOffset only runs under pv merge,
        # data_feed.cc:1855)
        raise ValueError(
            "rank_offset/ads_offset require pv-aligned batches: pass "
            "prebatched blocks or batch_counts (dataset.batch_bounds)")
    if counts is not None:
        over = [c for c in counts if c > batch_size]
        if over:
            raise ValueError(
                f"prebatched block of {over[0]} records exceeds batch_size "
                f"{batch_size}")
        n_batches = max(1, len(counts))
        pos = (np.concatenate(
            [i * batch_size + np.arange(c) for i, c in enumerate(counts)])
            if counts else np.zeros((0,), np.int64)).astype(np.int64)
        batch_real = np.asarray(counts + [0] * (n_batches - len(counts)),
                                np.int64)
        batch_base = np.concatenate([[0], np.cumsum(batch_real)[:-1]])
    else:
        n_batches = max(1, -(-n // batch_size))
        pos = slice(0, n)   # contiguous writes on the dense path
        batch_real = batch_base = None
    nb = n_batches * batch_size
    S, L = len(packer.sparse_slots), packer.capacity

    storage: List[np.ndarray] = []
    valid = store.take((nb,), bool, storage)
    valid[:] = False
    valid[pos] = True
    # the rows no record owns: a taken plane holds an earlier pass there
    pad = (np.flatnonzero(~valid) if isinstance(pos, np.ndarray)
           else slice(n, nb))

    def new_plane(shape, dtype, record_axis: int = 0) -> np.ndarray:
        """A plane from the store, its padding rows zeroed; the records'
        rows are the fan-out's to write."""
        plane = store.take(shape, dtype, storage)
        plane[(slice(None),) * record_axis + (pad,)] = 0
        return plane

    indices = new_plane((S, nb, L), np.int32, record_axis=1)
    lengths = new_plane((S, nb), np.int32, record_axis=1)

    def rows_of(r0: int, r1: int):
        """Plane rows of record range [r0, r1) — a contiguous slice on the
        dense path, a fancy-index slice of the position map otherwise."""
        return pos[r0:r1] if isinstance(pos, np.ndarray) else slice(r0, r1)

    def segments_of(r0: int, r1: int) -> List[tuple]:
        """Record range [r0, r1) as (block, lo, hi) pieces of the blocks."""
        b0 = int(np.searchsorted(bases, r0, side="right")) - 1
        b1 = int(np.searchsorted(bases, r1, side="left"))
        return [(blocks[bi], max(r0, int(bases[bi])) - int(bases[bi]),
                 min(r1, int(bases[bi + 1])) - int(bases[bi]))
                for bi in range(b0, b1)]

    def land(plane, col, width, cap, slot_of, lengths=None, check=None,
             translate=None):
        """The task per record range that lands one slot's records in
        ``plane[rows, col:col + width]`` (and their clipped lengths in
        ``lengths[rows]``); it answers with (values clipped away,
        strided?)."""
        def task(rows, segments):
            v, lens = _gather_ragged(segments, slot_of, store)
            total = len(v)
            if check is not None:
                check(v)
            if translate is not None:
                v = translate(v)
            kept, strided = _land_ragged(plane, rows, col, width, cap, v,
                                         lens)
            if lengths is not None:
                lengths[rows] = kept
            kept = int(kept.sum()) if isinstance(kept, np.ndarray) else \
                kept * len(lens)
            return total - kept, strided
        return task

    def fan_out(tasks) -> list:
        """Every task over every record range (as many as these tasks
        need to feed the pool); the tasks' answers."""
        ranges = [(rows_of(r0, r1), segments_of(r0, r1))
                  for r0, r1 in _record_ranges(n, pool.threads, len(tasks))]
        return pool.map(lambda t: t[0](*t[1]),
                        [(task, rng) for task in tasks for rng in ranges])

    def fits_int32(what: str):
        def check(v):
            if len(v) and int(v.max()) > np.iinfo(np.int32).max:
                raise ValueError(what)
        return check

    def sparse_task(si: int, slot):
        # translate the ragged values ONCE (real occurrences only), then
        # land the translated int32 rows; a record is clipped at ITS
        # slot's capacity, so positions at or beyond it hold padding in
        # every plane (the invariant ps/mxu_path.pull_pool_cvm relies on)
        return land(
            indices[si], 0, L, slot.capacity,
            lambda b: b.uint64_slots[slot.name], lengths=lengths[si],
            check=None if key_mapper is not None else fits_int32(
                "pack_pass without a key_mapper stores raw feasigns in the "
                "int32 index plane; keys exceed int32 — pass the engine's "
                "PassKeyMapper (engine.mapper)"),
            translate=key_mapper)

    try:
        # wave 1 — the heavy planes: every (sparse slot × record range)
        # task runs concurrently, each writing a disjoint [si, rows] region
        # of the planes (bit-identical at any thread count: no
        # accumulation, no ordering)
        done = fan_out([sparse_task(si, slot)
                        for si, slot in enumerate(packer.sparse_slots)])
        clipped = sum(c for c, _ in done)
        if clipped:
            stat_add("data.pack.clipped_keys", float(clipped))
        if on_plane is not None:
            on_plane("indices", indices)
            on_plane("lengths", lengths)

        # wave 2 — the light per-record planes (dense slots / label columns
        # / uid / aux), overlapping the caller's H2D dispatch of wave 1
        # when on_plane is staged
        dense = new_plane((nb, packer.dense_dim), np.float32)
        multi = new_plane((nb, len(packer.label_slots)), np.float32)
        uid = aux = None
        tasks = []
        col = 0
        for slot in packer.dense_slots:
            tasks.append(land(dense, col, slot.dim, slot.dim,
                              lambda b, k=slot.name: b.float_slots[k]))
            col += slot.dim
        for t, name in enumerate(packer.label_slots):
            if name in head.float_slots:
                tasks.append(land(multi, t, 1, 1,
                                  lambda b, k=name: b.float_slots[k]))
            elif name in head.uint64_slots:
                tasks.append(land(multi, t, 1, 1,
                                  lambda b, k=name: b.uint64_slots[k]))
            else:
                multi[:, t] = 0
        if feed_config.uid_slot:
            uid = new_plane((nb, 1), np.uint64)
            tasks.append(land(
                uid, 0, 1, 1,
                lambda b: b.uint64_slots[feed_config.uid_slot]))
            uid = uid[:, 0]
        if feed_config.string_slots or seq_key_slot:
            aux = {}
            # InputTable index planes (≙ InputTableDataFeed,
            # data_feed.h:2224)
            for slot in feed_config.string_slots:
                aux[slot.name] = new_plane((nb, slot.capacity), np.int32)
                tasks.append(land(aux[slot.name], 0, slot.capacity,
                                  slot.capacity,
                                  lambda b, k=slot.name: b.aux_slots[k]))
            if seq_key_slot:
                slot = next(s for s in packer.sparse_slots
                            if s.name == seq_key_slot)
                aux["seq_keys"] = new_plane((nb, slot.capacity), np.int32)
                tasks.append(trace.span("data.feed.seq_keys")(land(
                    aux["seq_keys"], 0, slot.capacity, slot.capacity,
                    lambda b, k=slot.name: b.uint64_slots[k],
                    check=fits_int32(f"seq_keys: keys of slot "
                                     f"{slot.name!r} exceed int32"))))
        landings = [s for _, s in done + fan_out(tasks)]
        stat_add("data.pack.groups_strided", float(sum(landings)))
        stat_add("data.pack.groups_scattered",
                 float(len(landings) - sum(landings)))
    finally:
        if own_pool is not None:
            own_pool.shutdown()
    labels = multi if len(packer.label_slots) > 1 else multi[:, 0]
    if on_plane is not None:
        on_plane("dense", dense)
        on_plane("labels", labels)
        on_plane("valid", valid)
        if aux:
            for name, plane in aux.items():
                on_plane(name, plane)

    ins_ids = None
    if head.ins_ids is not None:
        ins_ids = [i for b in blocks for i in (b.ins_ids or [])]
    out = HostPassArrays(indices=indices, lengths=lengths, dense=dense,
                         labels=labels, valid=valid, n_batches=n_batches,
                         batch_size=batch_size, num_real=n,
                         ins_ids=ins_ids, batch_real=batch_real,
                         batch_base=batch_base, aux=aux, uid=uid,
                         storage=storage)
    # wave 3 — pv planes, vectorized over the WHOLE pass (the former
    # per-batch python loops; bit-identical, see rank_offset.py) and
    # metered apart from the landing cost.  They read whole per-record
    # columns, the only ones still joined.
    t_planes = time.perf_counter()

    def column(name: str):
        if getattr(head, name) is None:
            return None
        return np.concatenate([getattr(b, name) for b in blocks])

    if feed_config.rank_offset or feed_config.ads_offset:
        search_ids = column("search_ids")
    if feed_config.rank_offset:
        # ≙ GetRankOffset per batch (data_feed.cc:1855) — batch-local row
        # indices; meaningful under pv grouping (whole pvs per batch)
        from paddlebox_tpu.data.rank_offset import build_rank_offset_batched
        out.rank_offset = build_rank_offset_batched(
            search_ids, column("cmatch"), column("rank"),
            batch_real, batch_base, batch_size, feed_config.max_rank)
        if on_plane is not None:
            on_plane("rank_offset", out.rank_offset)
    if feed_config.ads_offset:
        # ≙ GetAdsOffset per batch (data_feed.cc:3592): pv prefix offsets
        from paddlebox_tpu.data.rank_offset import build_ads_offset_batched
        out.ads_offset = build_ads_offset_batched(
            search_ids, batch_real, batch_base, batch_size)
        if on_plane is not None:
            on_plane("ads_offset", out.ads_offset)
    if head_keys is not None:
        if key_mapper is None:
            raise ValueError("head_keys need the pass's key_mapper: the "
                             "plane holds working-set rows")
        out.head_rows = np.ascontiguousarray(np.broadcast_to(
            np.asarray(key_mapper(np.asarray(head_keys, np.uint64)),
                       np.int32), (n_batches, len(head_keys))))
        if on_plane is not None:
            on_plane("head_rows", out.head_rows)
    if feed_config.rank_offset or feed_config.ads_offset:
        stat_observe("data.pass_feed.plane_build_s",
                     time.perf_counter() - t_planes)
    # pass-feed pack latency: whole-pass + amortized per-batch (the host
    # cost the pass-resident feed exists to keep out of the train loop)
    dt = time.perf_counter() - t_pack
    intervals.record("pack", m_pack, time.monotonic())
    stat_observe("data.pass_feed.pack_s", dt)
    stat_observe("data.pass_feed.batch_pack_s", dt / max(1, n_batches))
    return out


@dataclasses.dataclass
class PackedPassFeed:
    """Device-resident pass: stacked per-batch arrays + optional mxu plans.

    data layout (step-ready, so the hot loop does zero relayout):
      indices  [N, S, L, B] int32
      lengths  [N, S, B]    int32
      dense    [N, B, D]    float32
      labels   [N, B] / [N, B, T]
      valid    [N, B]       bool
    plans (mxu path): each of build_plan's outputs stacked on axis 0.
    """

    data: Dict[str, jnp.ndarray]
    n_batches: int
    batch_size: int
    num_real: int
    plans: Optional[Dict[str, jnp.ndarray]] = None
    plan_dims: object = None                # SpmmDims the plans were built for
    host: Optional[HostPassArrays] = None   # kept for dump/ins_ids paths
    uid: Optional[np.ndarray] = None        # [N*B] uint64 host-side uids
    host_labels: Optional[np.ndarray] = None  # [N*B(,T)] (uid_slot only)
    host_valid: Optional[np.ndarray] = None   # [N*B] bool (uid_slot only)
    # the PlaneStore buffers of the host planes this feed was uploaded
    # from, to hand back once a pass has trained on it (every transfer has
    # then completed); None where the feed keeps reading host planes
    # (``host``, ``uid``), which are then its own for good
    storage: Optional[List[np.ndarray]] = None

    def device_bytes(self) -> int:
        tot = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in self.data.values())
        if self.plans:
            tot += sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in self.plans.values())
        return tot


# module-level jits so every pass with the same geometry reuses the
# compiled relayout / plan-build executables (a fresh jit per pass would
# re-trace + re-compile — host work this path exists to eliminate)
@functools.partial(jax.jit, static_argnums=(1, 2))
def _relayout(d, N: int, B: int):
    s, nb, l = d["indices"].shape
    out = {
        # [S, N*B, L] -> [N, S, L, B]
        "indices": jnp.transpose(
            d["indices"].reshape(s, N, B, l), (1, 0, 3, 2)),
        "lengths": jnp.transpose(
            d["lengths"].reshape(s, N, B), (1, 0, 2)),
        "dense": d["dense"].reshape(N, B, -1),
        "valid": d["valid"].reshape(N, B),
    }
    lbl = d["labels"]
    out["labels"] = lbl.reshape((N, B) + lbl.shape[1:])
    for k in ("ads_offset", "head_rows"):   # per-BATCH planes [N, .]
        if k in d:
            out[k] = d[k]
    for k in d:   # extra per-record planes ([N*B, w] -> [N, B, w])
        if k not in out and k != "labels":
            out[k] = d[k].reshape(N, B, -1)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _build_plans(idx_all, dims, eff):
    from paddlebox_tpu.ops import sorted_spmm as sp

    def one(idx_slb):
        (rows2d, perm, inv_perm, ch, tl, fg, fs,
         first_occ) = sp.build_plan(idx_slb.reshape(-1), dims, eff)
        return {"rows2d": rows2d, "perm": perm, "inv_perm": inv_perm,
                "ch": ch, "tl": tl, "fg": fg, "fs": fs,
                "first_occ": first_occ}
    return jax.lax.map(one, idx_all)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _build_static_planes(plans, labels_all, slot_ids, dims, eff, shape_slb):
    """Static sorted-domain payload planes (per batch, feed-time):

      bs       [p_pad_kept] int32 — pooled-grad source index b*S + s of
               each kept sorted position (the push crossing gathers the
               [B*S, 1+D] dynamic grad matrix by this)
      labelcol [p_pad_kept] f32  — the occurrence's instance label
               (g_click never changes within a pass, so it never crosses)
      slotcol  [p_pad_kept] f32  — slot id x first_occ, pre-scaled so the
               hot step's slot column is a ready constant

    Everything derives from (plan.perm, labels, slot layout) — training-
    state-independent, so it belongs to the pass build, not the hot loop
    (≙ CopyForPush reading slot/label straight from the batch layout it
    owns, box_wrapper.cu:1168)."""
    s, l, b = shape_slb
    kd = eff or dims
    p0 = dims.p_pad - kd.p_pad

    def one(plan, labels_b):
        perm_full = jnp.concatenate(
            [plan["perm"],
             jnp.zeros((dims.p_pad - dims.p,), jnp.int32)])
        perm_k = perm_full[p0:]                    # kept sorted suffix
        s_of = perm_k // (l * b)
        b_of = perm_k % b
        labels1 = labels_b if labels_b.ndim == 1 else labels_b[:, 0]
        slotcol = (jnp.take(slot_ids.astype(jnp.float32), s_of)
                   * plan["first_occ"])
        return {
            "bs": (b_of * s + s_of).astype(jnp.int32),
            "labelcol": jnp.take(labels1.astype(jnp.float32), b_of),
            "slotcol": slotcol,
        }
    return jax.lax.map(lambda args: one(*args), (plans, labels_all))


def _h2d_sharding(name: str, sharding):
    """The H2D (pre-relayout) sharding of one SoA plane — record dim split
    over the mesh's dp axes so the full pass never materializes on one
    device; ads_offset (tiny per-batch plane) replicates."""
    if sharding is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = next(iter(sharding.values())).mesh
    spec = sharding["valid"].spec[1]    # the dp axes tuple
    if name == "indices":
        return NamedSharding(mesh, P(None, spec, None))
    if name == "lengths":
        return NamedSharding(mesh, P(None, spec))
    if name in ("dense", "labels", "valid"):
        return NamedSharding(mesh, P(spec))
    if name in ("ads_offset", "head_rows"):
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(spec, None))   # rank_offset / aux planes


def _put_plane(name: str, a: np.ndarray, sharding):
    sh = _h2d_sharding(name, sharding)
    return jnp.asarray(a) if sh is None else jax.device_put(a, sh)


class PlaneStager:
    """Overlap H2D with pack: pack_pass invokes this (``on_plane``) as
    each SoA plane finishes, dispatching its ``device_put`` immediately so
    the upload hides behind the remaining host pack; ``upload_pass`` then
    skips the already-staged planes.  Dispatch happens on the pack
    coordinator's thread only — never on pool workers (concurrent device
    dispatch from several python threads can deadlock single-stream
    runtimes, ps/pass_manager.py)."""

    def __init__(self, sharding=None):
        self.sharding = sharding
        self.staged: Dict[str, jnp.ndarray] = {}

    def __call__(self, name: str, a: np.ndarray) -> None:
        t0 = time.monotonic()
        self.staged[name] = _put_plane(name, a, self.sharding)
        intervals.record("upload", t0, time.monotonic())


@trace.span("data.feed.upload_enqueue")
def upload_pass(host_arrays: HostPassArrays, keep_host: bool = False,
                sharding=None, staged=None) -> PackedPassFeed:
    """H2D once + one relayout jit into the step-ready stacked layout.

    sharding: optional {name: jax.sharding.Sharding} — under a topology the
    batch dims shard dp-wise so the resident pass is distributed, matching
    the per-batch path's _put_batch placement.  The H2D upload itself is
    already sharded (record dim split over the mesh) so the full pass never
    materializes on a single device; the relayout then runs under GSPMD and
    the result is device_put to the final batch-dim shardings.

    staged: optional PlaneStager (or its dict) holding planes whose H2D
    was already dispatched during pack — those skip the put here; with no
    stager every plane uploads all-at-once (the parallel-packer-off
    path).

    The span times the ENQUEUE of the transfers and the relayout (jax
    returns before the device has the bytes), hence its name."""
    m_up = time.monotonic()
    h = host_arrays
    N, B = h.n_batches, h.batch_size
    pre = dict(getattr(staged, "staged", staged) or {})

    def put(name, a):
        if name in pre:
            return pre[name]
        return _put_plane(name, a, sharding)

    dev = {
        "indices": put("indices", h.indices),   # [S, N*B, L]
        "lengths": put("lengths", h.lengths),
        "dense": put("dense", h.dense),
        "labels": put("labels", h.labels),
        "valid": put("valid", h.valid),
    }
    for k, v in h.extra_planes().items():
        dev[k] = put(k, v)
    if h.ads_offset is not None:
        # tiny per-batch plane, replicated over the mesh (a plain
        # process-local array cannot mix with global arrays under jit)
        dev["ads_offset"] = put("ads_offset", h.ads_offset)
    if h.head_rows is not None:
        dev["head_rows"] = put("head_rows", h.head_rows)
    data = _relayout(dev, N, B)
    if sharding is not None:
        data = {k: jax.device_put(v, sharding[k]) if k in sharding else v
                for k, v in data.items()}
    intervals.record("upload", m_up, time.monotonic())
    owns = keep_host or h.uid is not None
    return PackedPassFeed(data=data, n_batches=N, batch_size=B,
                          num_real=h.num_real,
                          host=h if keep_host else None, uid=h.uid,
                          host_labels=h.labels if h.uid is not None else None,
                          host_valid=h.valid if h.uid is not None else None,
                          storage=None if owns else h.storage)


def precompute_plans(feed: PackedPassFeed, dims, eff=None,
                     slot_ids=None) -> None:
    """Per-batch sorted-spmm plans, built on device in one jit and kept
    resident (≙ the pass-scope dedup/index build of box_wrapper_impl.h:129:
    the sort is data-independent of the training state, so it runs once at
    pass build, never in the hot step).

    eff (sorted_spmm.trimmed_dims, shared by ALL batches so the stacked
    plan arrays are homogeneous): trim leading padding occurrences from the
    kernel worklist — the caller derives it from the max real-occurrence
    count over the pass's batches.

    slot_ids [S]: also build the static payload planes (bs/labelcol/
    slotcol — see _build_static_planes) so the push crossing moves only the
    dynamic 1+D grad columns.  Multi-task feeds (labels [N, B, T]) use
    per-task cvm columns at step time, so planes are built only for 1-D
    (or single-column) labels."""
    feed.plans = _build_plans(feed.data["indices"], dims, eff)
    feed.plan_dims = dims
    labels = feed.data["labels"]
    if slot_ids is not None and (labels.ndim == 2 or labels.shape[-1] == 1):
        n, s, l, b = feed.data["indices"].shape
        feed.plans.update(_build_static_planes(
            feed.plans, labels, jnp.asarray(slot_ids), dims, eff,
            (s, l, b)))


def slice_batch(tree, i):
    """Batch i of a stacked pytree (XLA dynamic-slice inside jit)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def plan_tuple(p: Dict[str, jnp.ndarray]):
    """Plans dict (one batch) → the positional tuple build_plan returns —
    single source of the field order for every consumer.  When the static
    payload planes are present (precompute_plans with slot_ids) the tuple
    extends to 11 fields; mxu_path keys the narrow-crossing push on the
    length."""
    base = (p["rows2d"], p["perm"], p["inv_perm"], p["ch"], p["tl"],
            p["fg"], p["fs"], p["first_occ"])
    if "bs" in p:
        return base + (p["bs"], p["labelcol"], p["slotcol"])
    return base
