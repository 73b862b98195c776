"""ctypes wrapper over the native parser/hash library.

A block that ``NativeChunk.take`` makes lies in one buffer: every
``(offsets, values)`` pair is a typed view of it, a cache line apart.  A
chunk that was given a ``BlockStore`` (``new_chunk(store)``: the reading
``SlotDataset``'s, the rebuild's ``SlotObjPool``, data_feed.h:305) asks
it for that buffer, so a pass is parsed into the slabs the last pass's
blocks gave back, and leaves the slab on ``block.storage`` for the
dataset to give back in turn; the block is valid until then
(data/slot_record.py has the lifetime contract).
"""

from __future__ import annotations

import ctypes
import threading
from typing import Optional

import numpy as np

from paddlebox_tpu.config import DataFeedConfig
from paddlebox_tpu.data.slot_record import (ALIGN, BlockStore,
                                             SlotRecordBlock)
from paddlebox_tpu.native import build
from paddlebox_tpu.utils.monitor import stat_add

_lib = None
_lib_lock = threading.Lock()


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not build.ensure_built():
            return None
        lib = ctypes.CDLL(build.lib_path())
        lib.pbox_parse_block.restype = ctypes.c_void_p
        lib.pbox_parse_block.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32)]
        lib.pbox_parse_block_bytes.restype = ctypes.c_void_p
        lib.pbox_parse_block_bytes.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32)]
        lib.pbox_slot_total.restype = ctypes.c_int64
        lib.pbox_slot_total.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        for name in ("pbox_fill_slot_u64", "pbox_fill_slot_f32"):
            fn = getattr(lib, name)
            fn.restype = None
            fn.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                           ctypes.c_void_p, ctypes.c_void_p]
        lib.pbox_fill_logkeys.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_void_p, ctypes.c_void_p]
        lib.pbox_insid_bytes.restype = ctypes.c_int64
        lib.pbox_insid_bytes.argtypes = [ctypes.c_void_p]
        lib.pbox_fill_insids.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                         ctypes.c_void_p]
        lib.pbox_free.argtypes = [ctypes.c_void_p]
        lib.pbox_clear.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


BYTES_SUFFIX = "_bytes"     # <entry>_bytes takes a file's bytes as they lie


class NativeSlotParser:
    """Drop-in replacement for data_feed.SlotParser.parse_block, and a
    parser that takes a file's bytes as they are (``takes_bytes``,
    ``new_chunk``), with no Python string per line."""

    def __init__(self, config: DataFeedConfig, parse_ins_id: bool = False,
                 parse_logkey: bool = False):
        self.config = config
        self.parse_ins_id = parse_ins_id
        self.parse_logkey = parse_logkey
        self._is_float = np.array(
            [1 if s.dtype == "float" else 0 for s in config.slots], np.uint8)

    # plugin .so overrides (ParserPluginManager sets these to dlopen'd
    # site-specific parsers exposing the same ABI)
    _lib = None
    _entry = "pbox_parse_block"

    def _resolve(self, suffix: str = ""):
        """The parse entry ``<_entry><suffix>``: the canonical library's,
        or a plugin .so's with the canonical ABI stamped on it.  Accessors
        (slot_total/fill_*) always come from the canonical lib — a plugin
        .so only overrides the *parse* entries and must return a handle
        compatible with the canonical block layout."""
        canonical = getattr(_load(), "pbox_parse_block" + suffix)
        if self._lib is None:
            return canonical
        entry = getattr(self._lib, self._entry + suffix)
        # ctypes defaults restype to c_int (truncates the handle pointer)
        entry.restype = ctypes.c_void_p
        entry.argtypes = canonical.argtypes
        return entry

    @property
    def takes_bytes(self) -> bool:
        """A plugin .so written against the block ABI alone has no
        ``<entry>_bytes``: its files keep the text loop."""
        try:
            self._resolve(BYTES_SUFFIX)
        except AttributeError:
            return False
        return True

    def new_chunk(self, store: Optional[BlockStore] = None) -> "NativeChunk":
        return NativeChunk(self, store)

    def parse_block(self, lines) -> SlotRecordBlock:
        chunk = NativeChunk(self)
        try:
            chunk.feed_lines(lines)
            return chunk.take()
        finally:
            chunk.close()


class NativeChunk:
    """One block under construction: a native handle that takes records
    from lines or from successive byte ranges of a file, and that ``take``
    turns into a SlotRecordBlock.  The handle lives until ``close`` and is
    emptied, not freed, between blocks: its columns keep their memory, so
    a file's later chunks grow nothing and fault no fresh page.  With a
    ``store`` the blocks' own memory is recycled too (``take``).  One thread
    at a time."""

    def __init__(self, parser: NativeSlotParser,
                 store: Optional[BlockStore] = None):
        self._parser = parser
        self._store = store
        self._bytes_entry = None
        self._handle = None
        self.n = 0                  # records held

    def _config_args(self):
        p = self._parser
        return (len(p.config.slots),
                p._is_float.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                int(p.parse_ins_id), int(p.parse_logkey))

    def _adopt(self, handle, n_records: int, status: int) -> None:
        if not handle:      # the native side freed what it held
            self._handle, self.n = None, 0
            raise ValueError(
                f"native parse failed (status={status}); check slot "
                f"config against the data "
                f"(n_slots={len(self._parser.config.slots)})")
        self._handle, self.n = handle, n_records

    def feed_lines(self, lines) -> None:
        """All of ``lines``, into a fresh handle (the block ABI)."""
        self.close()
        buf = ("\n".join(lines) + "\n").encode()
        n_rec, status = ctypes.c_int64(0), ctypes.c_int32(0)
        handle = self._parser._resolve()(
            buf, len(buf), *self._config_args(),
            ctypes.byref(n_rec), ctypes.byref(status))
        self._adopt(handle, n_rec.value, status.value)

    def feed_bytes(self, buf: np.ndarray, lo: int, hi: int,
                   max_records: int) -> int:
        """Append whole lines of ``buf[lo:hi]`` (uint8) until the chunk
        holds ``max_records``; returns the bytes consumed.  A last line
        that lacks its newline is left for the caller to carry over."""
        if buf.dtype != np.uint8 or not buf.flags.c_contiguous \
                or not 0 <= lo <= hi <= buf.shape[0]:
            raise ValueError("feed_bytes takes buf[lo:hi] of a contiguous "
                             "uint8 array")
        if self._bytes_entry is None:
            self._bytes_entry = self._parser._resolve(BYTES_SUFFIX)
        n_rec, status = ctypes.c_int64(0), ctypes.c_int32(0)
        consumed = ctypes.c_int64(0)
        handle = self._bytes_entry(
            self._handle, buf.ctypes.data + lo, hi - lo, max_records,
            *self._config_args(), ctypes.byref(n_rec),
            ctypes.byref(consumed), ctypes.byref(status))
        self._adopt(handle, n_rec.value, status.value)
        return consumed.value

    def take(self) -> SlotRecordBlock:
        """The records held, as a block; the chunk is empty again.  The
        block's arrays are typed views, a cache line apart, of one buffer:
        a slab of the chunk's ``BlockStore`` (``block.storage``, which the
        block's dataset gives back when the block is dead), or with no
        store an array of its own."""
        handle, n = self._handle, self.n
        if handle is None:
            return SlotRecordBlock(n=0)
        p, lib = self._parser, _load()
        # (dtype, count) of every array, in carving order
        wanted = []
        for si, slot in enumerate(p.config.slots):
            wanted += [(np.int64, n + 1),
                       (np.float32 if slot.dtype == "float" else np.uint64,
                        lib.pbox_slot_total(handle, si))]
        if p.parse_logkey:
            wanted += [(np.uint64, n), (np.int32, n), (np.int32, n)]
        starts, end = [], 0
        for dtype, count in wanted:
            starts.append(end)
            end += -(-count * np.dtype(dtype).itemsize // ALIGN) * ALIGN
        block = SlotRecordBlock(n=n)
        if self._store is not None:
            buf = block.storage = self._store.take(end)
        else:
            buf = np.empty(end, np.uint8)
        arrays = iter([np.ndarray((count,), dtype, buffer=buf, offset=lo)
                       for lo, (dtype, count) in zip(starts, wanted)])
        for si, slot in enumerate(p.config.slots):
            offsets, values = next(arrays), next(arrays)
            if slot.dtype == "float":
                lib.pbox_fill_slot_f32(handle, si, values.ctypes.data,
                                       offsets.ctypes.data)
                block.float_slots[slot.name] = (values, offsets)
            else:
                lib.pbox_fill_slot_u64(handle, si, values.ctypes.data,
                                       offsets.ctypes.data)
                block.uint64_slots[slot.name] = (values, offsets)
        if p.parse_logkey:
            sids, cm, rk = arrays
            lib.pbox_fill_logkeys(handle, sids.ctypes.data,
                                  cm.ctypes.data, rk.ctypes.data)
            block.search_ids, block.cmatch, block.rank = sids, cm, rk
        if p.parse_ins_id or p.parse_logkey:
            nbytes = lib.pbox_insid_bytes(handle)
            chars = ctypes.create_string_buffer(max(nbytes, 1))
            offs = np.empty(n + 1, np.int64)
            lib.pbox_fill_insids(handle, chars, offs.ctypes.data)
            raw = chars.raw[:nbytes].decode()
            block.ins_ids = [raw[offs[i]:offs[i + 1]] for i in range(n)]
        stat_add("stat_total_feasign_num_in_mem", block.feasign_count)
        lib.pbox_clear(handle)
        self.n = 0
        return block

    def close(self) -> None:
        if self._handle is not None:
            _load().pbox_free(self._handle)
        self._handle, self.n = None, 0


class NativeHashShard:
    """uint64 → dense-row map (see hash_shard.cc)."""

    def __init__(self, capacity_hint: int = 1024):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        if not hasattr(lib, "_hash_proto_done"):
            lib.pbox_hash_new.restype = ctypes.c_void_p
            lib.pbox_hash_new.argtypes = [ctypes.c_int64]
            lib.pbox_hash_free.argtypes = [ctypes.c_void_p]
            lib.pbox_hash_size.restype = ctypes.c_int64
            lib.pbox_hash_size.argtypes = [ctypes.c_void_p]
            for nm in ("pbox_hash_upsert", "pbox_hash_find"):
                fn = getattr(lib, nm)
                fn.restype = None
                fn.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_int64, ctypes.c_void_p]
            lib.pbox_hash_keys.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            lib._hash_proto_done = True
        self._lib = lib
        self._h = lib.pbox_hash_new(capacity_hint)

    def __del__(self):
        try:
            self._lib.pbox_hash_free(self._h)
        except Exception:
            pass

    def __len__(self):
        return self._lib.pbox_hash_size(self._h)

    def upsert(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.uint64)
        rows = np.empty(len(keys), np.int64)
        self._lib.pbox_hash_upsert(self._h, keys.ctypes.data, len(keys),
                                   rows.ctypes.data)
        return rows

    def find(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.uint64)
        rows = np.empty(len(keys), np.int64)
        self._lib.pbox_hash_find(self._h, keys.ctypes.data, len(keys),
                                 rows.ctypes.data)
        return rows

    def keys_by_row(self) -> np.ndarray:
        out = np.empty(len(self), np.uint64)
        self._lib.pbox_hash_keys(self._h, out.ctypes.data)
        return out
