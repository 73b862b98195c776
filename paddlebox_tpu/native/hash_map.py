"""ctypes wrapper over the native uint64→row hash (hash_shard.cc).

Three users:
* PassKeyMapper (ps/embedding.py): pass-scope key→row translation — the
  once-per-pass DedupKeysAndFillIdx equivalent (box_wrapper_impl.h:129);
  ~6x faster than np.searchsorted over a 2M-key array at 13M+ lookups.
* ShardedHostTable (ps/host_table.py): DRAM-tier key→row resolution.
* BoxPSEngine (ps/pass_manager.py): the pass's key dedup over the readers'
  key chunks in place (KeyDedup).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

import numpy as np

from paddlebox_tpu.native import build

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
        lib.pbox_hash_new.restype = ctypes.c_void_p
        lib.pbox_hash_new.argtypes = [ctypes.c_int64]
        lib.pbox_hash_free.argtypes = [ctypes.c_void_p]
        lib.pbox_hash_size.restype = ctypes.c_int64
        lib.pbox_hash_size.argtypes = [ctypes.c_void_p]
        lib.pbox_hash_upsert.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
        lib.pbox_hash_find.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
        lib.pbox_hash_keys.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.pbox_hash_find_rows1_i32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int32]
        lib.pbox_dedup_new.restype = ctypes.c_void_p
        lib.pbox_dedup_new.argtypes = []
        lib.pbox_dedup_free.argtypes = [ctypes.c_void_p]
        lib.pbox_dedup_run.restype = ctypes.c_int64
        lib.pbox_dedup_run.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int32]
        lib.pbox_dedup_take.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _default_threads() -> int:
    return min(8, os.cpu_count() or 1)


class NativeKeyHash:
    """uint64 key → dense row id (insertion order), native open addressing."""

    def __init__(self, capacity_hint: int = 16):
        lib = _load()
        if lib is None:
            raise RuntimeError("native hash library unavailable")
        self._lib = lib
        self._h = lib.pbox_hash_new(int(capacity_hint))

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.pbox_hash_free(h)
            self._h = None

    def __len__(self) -> int:
        return int(self._lib.pbox_hash_size(self._h))

    def upsert(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.empty((len(keys),), np.int64)
        self._lib.pbox_hash_upsert(
            self._h, keys.ctypes.data_as(ctypes.c_void_p), len(keys),
            out.ctypes.data_as(ctypes.c_void_p))
        return out

    def find(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.empty((len(keys),), np.int64)
        self._lib.pbox_hash_find(
            self._h, keys.ctypes.data_as(ctypes.c_void_p), len(keys),
            out.ctypes.data_as(ctypes.c_void_p))
        return out

    def find_rows1_i32(self, keys: np.ndarray,
                       n_threads: Optional[int] = None) -> np.ndarray:
        """key → insertion-row + 1; 0 for missing and for key 0 (the
        reserved zero-embedding row).  Threaded (read-only probes)."""
        if n_threads is None:
            n_threads = _default_threads()
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.empty((len(keys),), np.int32)
        self._lib.pbox_hash_find_rows1_i32(
            self._h, keys.ctypes.data_as(ctypes.c_void_p), len(keys),
            out.ctypes.data_as(ctypes.c_void_p), int(n_threads))
        return out


class KeyDedup:
    """The distinct nonzero keys of a list of uint64 chunks, ascending —
    ``np.unique(np.concatenate(chunks))`` without key 0, read from the
    chunks where they lie.  Range-split across threads (one below 64k
    keys, as ``find_rows1_i32``); the per-range sets are kept from call to
    call, so only the result is allocated once they have grown to a pass."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native hash library unavailable")
        self._lib = lib
        self._h = lib.pbox_dedup_new()
        self._lock = threading.Lock()  # one call at a time on the scratch

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.pbox_dedup_free(h)
            self._h = None

    def __call__(self, chunks, n_threads: Optional[int] = None) -> np.ndarray:
        if n_threads is None:
            n_threads = _default_threads()
        # contiguous uint64 views: no copy for what the readers hand over
        chunks = [np.ascontiguousarray(c, dtype=np.uint64) for c in chunks]
        ptrs = np.array([c.ctypes.data for c in chunks], np.uintp)
        lens = np.array([len(c) for c in chunks], np.int64)
        with self._lock:
            n = self._lib.pbox_dedup_run(
                self._h, ptrs.ctypes.data_as(ctypes.c_void_p),
                lens.ctypes.data_as(ctypes.c_void_p), len(chunks),
                int(n_threads))
            out = np.empty((n,), np.uint64)
            self._lib.pbox_dedup_take(
                self._h, out.ctypes.data_as(ctypes.c_void_p))
        return out
