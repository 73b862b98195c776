"""SlotRecord storage: struct-of-arrays blocks of instances, and the pool
their memory comes from.

TPU-first redesign of the reference's per-record SlotRecordObject + arena pool
(data_feed.h:97-440: SlotValues, SlotRecordObject, SlotObjPool).  Instead of
millions of tiny heap records recycled through a pool, instances travel in
*blocks*: one contiguous (values, lod-offsets) pair per slot for a batch of
records.  This keeps host memory flat and copies vectorized — the role the
arena played for C++ — and is exactly the layout the device batch-pack wants
(SURVEY.md §7 step 2).

``BlockStore`` is the rebuild's ``SlotObjPool`` (data_feed.h:305: bulk
get/put of records): a block parsed by the native reader lies in ONE slab,
its arrays typed views of it, and a pass's slabs go back to the store when
the pass's blocks are dead, so the next pass is parsed into memory that has
been touched before (a fresh page costs a fault, and a pass is ~230k of them).

**Block lifetime.**  A ``SlotRecordBlock`` handed out by a ``SlotDataset``
(``get_blocks``) is valid until that dataset next replaces its blocks:
``load_into_memory``, ``release_memory``, ``wait_preload_done``, a shuffle or
``preprocess_instance``.  Its storage is then given back and will be
overwritten by a later read, and the block itself is retired: reading its
slots raises.  Whoever keeps data longer copies it; ``concat`` (one block or
many), ``select`` / ``permute`` / ``slice`` and ``all_keys`` all return arrays
of their own.
"""

from __future__ import annotations

import collections.abc
import dataclasses
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from paddlebox_tpu.utils.monitor import stat_add

Ragged = Tuple[np.ndarray, np.ndarray]  # (values [total], offsets [n+1])


def _empty_ragged(dtype) -> Ragged:
    return (np.empty((0,), dtype=dtype), np.zeros((1,), dtype=np.int64))


def _concat_ragged(parts: Sequence[Ragged], dtype) -> Ragged:
    values = np.concatenate([p[0] for p in parts]) if parts else \
        np.empty((0,), dtype=dtype)
    lens = np.concatenate([np.diff(p[1]) for p in parts]) if parts else \
        np.empty((0,), dtype=np.int64)
    offsets = np.zeros((len(lens) + 1,), dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    return values, offsets


def _select_ragged(r: Ragged, idx: np.ndarray) -> Ragged:
    values, offsets = r
    lens = np.diff(offsets)[idx]
    new_off = np.zeros((len(idx) + 1,), dtype=np.int64)
    np.cumsum(lens, out=new_off[1:])
    # gather value spans of the selected records
    starts = offsets[idx]
    total = int(new_off[-1])
    flat_idx = np.empty((total,), dtype=np.int64)
    # vectorized span expansion: for each selected record j with length l_j,
    # flat_idx[new_off[j]:new_off[j+1]] = starts[j] + [0..l_j)
    if total:
        rep_starts = np.repeat(starts - new_off[:-1], lens)
        flat_idx = np.arange(total, dtype=np.int64) + rep_starts
    return values[flat_idx], new_off


PAGE = 4096
ALIGN = 64      # every array carved from a slab starts on a cache line


def size_class(nbytes: int) -> int:
    """``nbytes`` rounded up to a page, then to eight classes a power of
    two (at most an eighth over): blocks whose slots hold a varying number
    of keys still find each other's slabs."""
    nbytes = max(PAGE, -(-nbytes // PAGE) * PAGE)
    step = max(PAGE, 1 << max(0, (nbytes - 1).bit_length() - 4))
    return -(-nbytes // step) * step


def aligned_slab(nbytes: int) -> np.ndarray:
    """``nbytes`` uninitialised bytes that start on a page."""
    raw = np.empty(nbytes + PAGE, np.uint8)
    lo = -raw.ctypes.data % PAGE
    return raw[lo:lo + nbytes]


class BlockStore:
    """Storage of parsed blocks, recycled from pass to pass (≙ SlotObjPool,
    data_feed.h:305).  ``take`` answers with a page-aligned ``uint8`` slab of
    the request's size class: the smallest one given back that can hold it,
    else a new one.  ``release_pass`` takes the slabs of a pass whose blocks
    are dead and lets go of whatever else it held, so the store never holds
    more bytes than the last released pass did; ``give_back`` returns single
    slabs (a block dropped while its pass is still being read).  Thread-safe;
    it learns nothing from its callers but sizes, and a store that has
    nothing large enough simply allocates."""

    def __init__(self):
        self._lock = threading.Lock()
        self._free: Dict[int, List[np.ndarray]] = {}    # class -> slabs
        self._free_bytes = 0

    @property
    def free_bytes(self) -> int:
        return self._free_bytes

    def take(self, nbytes: int) -> np.ndarray:
        want = size_class(nbytes)
        slab = None
        with self._lock:
            fit = min((c for c, slabs in self._free.items()
                       if c >= want and slabs), default=None)
            if fit is not None:
                slab = self._free[fit].pop()
                self._free_bytes -= slab.nbytes
        if slab is not None:
            stat_add("data.read.block_bytes_reused", nbytes)
            return slab
        stat_add("data.read.block_bytes_fresh", nbytes)
        return aligned_slab(want)

    def give_back(self, slabs: Iterable[np.ndarray]) -> None:
        with self._lock:
            for slab in slabs:
                self._free.setdefault(slab.nbytes, []).append(slab)
                self._free_bytes += slab.nbytes

    def release_pass(self, slabs: Sequence[np.ndarray]) -> None:
        """The slabs of a whole pass: they become all the store holds."""
        if not slabs:
            return
        with self._lock:
            self._free, self._free_bytes = {}, 0
        self.give_back(slabs)


class _RetiredSlots(collections.abc.Mapping):
    """What a retired block has in place of its slots: any read raises."""

    def _raise(self, *_):
        raise RuntimeError(
            "SlotRecordBlock used after its dataset replaced it "
            "(load_into_memory / release_memory / a shuffle): a block is "
            "valid until then, copy what must outlive it")

    __getitem__ = __iter__ = __len__ = _raise


_RETIRED = _RetiredSlots()


@dataclasses.dataclass
class SlotRecordBlock:
    """A batch of instances in struct-of-arrays layout."""

    n: int
    uint64_slots: Dict[str, Ragged] = dataclasses.field(default_factory=dict)
    float_slots: Dict[str, Ragged] = dataclasses.field(default_factory=dict)
    # aux index slots (InputTable-resolved string keys) — NOT feasigns:
    # excluded from all_keys() so they never register in the PS pass build
    aux_slots: Dict[str, Ragged] = dataclasses.field(default_factory=dict)
    ins_ids: Optional[List[str]] = None
    search_ids: Optional[np.ndarray] = None   # uint64, PV/AucRunner merge key
    cmatch: Optional[np.ndarray] = None       # int32
    rank: Optional[np.ndarray] = None         # int32
    # the BlockStore slab the arrays above are views of (native reader);
    # None for a block that owns ordinary arrays
    storage: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    @property
    def feasign_count(self) -> int:
        return sum(int(v[1][-1]) for v in self.uint64_slots.values())

    def retire(self) -> Optional[np.ndarray]:
        """Detach the slab for its store; the block reads as dead after."""
        slab, self.storage = self.storage, None
        self.uint64_slots = self.float_slots = self.aux_slots = _RETIRED
        self.ins_ids = self.search_ids = self.cmatch = self.rank = None
        return slab

    def select(self, idx: np.ndarray) -> "SlotRecordBlock":
        idx = np.asarray(idx, dtype=np.int64)
        out = SlotRecordBlock(n=len(idx))
        out.uint64_slots = {k: _select_ragged(v, idx)
                            for k, v in self.uint64_slots.items()}
        out.float_slots = {k: _select_ragged(v, idx)
                           for k, v in self.float_slots.items()}
        out.aux_slots = {k: _select_ragged(v, idx)
                         for k, v in self.aux_slots.items()}
        if self.ins_ids is not None:
            out.ins_ids = [self.ins_ids[i] for i in idx]
        for f in ("search_ids", "cmatch", "rank"):
            v = getattr(self, f)
            if v is not None:
                setattr(out, f, v[idx])
        return out

    def permute(self, idx: np.ndarray) -> "SlotRecordBlock":
        return self.select(idx)

    def slice(self, start: int, stop: int) -> "SlotRecordBlock":
        return self.select(np.arange(start, min(stop, self.n)))

    @staticmethod
    def concat(blocks: Sequence["SlotRecordBlock"]) -> "SlotRecordBlock":
        blocks = [b for b in blocks if b.n > 0]
        if not blocks:
            return SlotRecordBlock(n=0)
        out = SlotRecordBlock(n=sum(b.n for b in blocks))
        u_keys = blocks[0].uint64_slots.keys()
        f_keys = blocks[0].float_slots.keys()
        out.uint64_slots = {
            k: _concat_ragged([b.uint64_slots[k] for b in blocks], np.uint64)
            for k in u_keys}
        out.float_slots = {
            k: _concat_ragged([b.float_slots[k] for b in blocks], np.float32)
            for k in f_keys}
        out.aux_slots = {
            k: _concat_ragged([b.aux_slots[k] for b in blocks], np.uint64)
            for k in blocks[0].aux_slots.keys()}
        if blocks[0].ins_ids is not None:
            out.ins_ids = [i for b in blocks for i in (b.ins_ids or [])]
        for f in ("search_ids", "cmatch", "rank"):
            if getattr(blocks[0], f) is not None:
                setattr(out, f, np.concatenate([getattr(b, f) for b in blocks]))
        return out

    def all_keys(self) -> np.ndarray:
        """Every uint64 feasign in the block (with repeats) — feeds the
        pass working-set build (≙ MergeInsKeys data_set.cc:2293)."""
        parts = [v[0] for v in self.uint64_slots.values()]
        if not parts:
            return np.empty((0,), dtype=np.uint64)
        return np.concatenate(parts)
