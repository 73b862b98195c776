"""Build the native runtime library (g++ → .so, loaded via ctypes).

≙ the reference's cmake native build for the framework runtime; kept
dependency-free: compiled on first use into the package dir.  The rebuild
check is keyed on CONTENT — a sha256 over the compiler flags, the build
host's CPU feature set (the flags include ``-march=native``, so a binary
copied from another machine must not be trusted) and every source file —
stored in a sidecar beside the .so.  mtimes are not consulted: a fresh
checkout or a disk copy resets them.

A failed build or load selects the pure-Python fallbacks (tests rely on
that), but never silently: one warning carries the compiler's stderr, and
the ``native.lib_ok`` stat says which side of the choice this process is
on.
"""

from __future__ import annotations

import hashlib
import logging
import os
import platform
import subprocess
import threading

from paddlebox_tpu.utils.monitor import stat_set

log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["slot_parser.cc", "hash_shard.cc", "dump_writer.cc"]
_FLAGS = ["-O3", "-march=native", "-shared", "-fPIC", "-std=c++17"]
_LIB = os.path.join(_DIR, "_libpbox_native.so")
_KEY = _LIB + ".key"
_LOCK = threading.Lock()
# outcome of this process's first ensure_built (None = not tried yet) and
# the fallback sites already warned about — both guarded by _LOCK
_STATUS = {"ok": None, "rebuilt": False, "error": ""}
_WARNED = set()


def lib_path() -> str:
    return _LIB


def status() -> dict:
    """{"ok", "rebuilt", "error"} of this process's build-or-reuse."""
    with _LOCK:
        return dict(_STATUS)


def _cpu_identity() -> str:
    """What ``-march=native`` resolved against on this host."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.machine() + "/" + platform.processor()


def _build_key(srcs) -> str:
    h = hashlib.sha256()
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_identity().encode())
    for s in srcs:
        h.update(os.path.basename(s).encode())
        with open(s, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _read_key() -> str:
    try:
        with open(_KEY) as f:
            return f.read().strip()
    except OSError:
        return ""


def _fail(error: str) -> bool:
    _STATUS.update(ok=False, error=error)
    stat_set("native.lib_ok", 0.0)
    log.warning("native library unavailable — host paths fall back to "
                "pure Python (slot parser, key hash, dump writer): %s",
                error)
    return False


def _ok(rebuilt: bool) -> bool:
    _STATUS.update(ok=True, rebuilt=rebuilt)
    stat_set("native.lib_ok", 1.0)
    return True


def ensure_built() -> bool:
    """Compile if missing/stale. Returns True when the .so is usable."""
    with _LOCK:
        if _STATUS["ok"] is not None:
            return _STATUS["ok"]
    # the reads are idempotent, so they stay outside the latch
    srcs = [os.path.join(_DIR, s) for s in _SOURCES]
    missing = [s for s in srcs if not os.path.exists(s)]
    key = "" if missing else _build_key(srcs)
    current = os.path.exists(_LIB) and _read_key() == key
    with _LOCK:
        if _STATUS["ok"] is not None:       # another thread got here first
            return _STATUS["ok"]
        if missing:
            return _fail(f"sources missing: {missing}")
        if current:
            return _ok(rebuilt=False)
        # build beside the target and rename into place: a concurrent
        # process never loads a half-written library
        tmp = f"{_LIB}.tmp.{os.getpid()}"
        cmd = ["g++"] + _FLAGS + ["-o", tmp] + srcs
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=240)
        except (OSError, subprocess.TimeoutExpired) as e:
            return _fail(f"{type(e).__name__}: {e}")
        if proc.returncode != 0:
            if os.path.exists(tmp):
                os.unlink(tmp)
            return _fail(f"g++ rc={proc.returncode}\n{proc.stderr}")
        os.replace(tmp, _LIB)
        # pboxlint: disable-next=PB104 -- build-once latch: every waiter needs the library this block produces, and the sidecar is 65 bytes
        with open(tmp, "w") as f:
            f.write(key + "\n")
        os.replace(tmp, _KEY)
        return _ok(rebuilt=True)


def warn_fallback(site: str, err: BaseException) -> None:
    """A caller that holds a Python fallback lost the native path to an
    unexpected error (load failure, ABI mismatch): say so once per site."""
    with _LOCK:
        if site in _WARNED:
            return
        _WARNED.add(site)
    stat_set("native.lib_ok", 0.0)
    log.warning("native %s unavailable, using the Python fallback: %s: %s",
                site, type(err).__name__, err)
