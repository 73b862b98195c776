"""Global flag registry.

TPU-native equivalent of the reference's gflags layer
(paddle/fluid/platform/flags.cc — e.g. the PaddleBox block at flags.cc:946-975:
enable_pullpush_dedup_keys, padbox_record_pool_max_size,
padbox_dataset_shuffle_thread_num, ...).  Flags are plain Python values with
defaults, overridable by environment variables ``FLAGS_<name>`` at first read
and programmatically via :func:`set_flags` (mirroring ``paddle.set_flags``).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict

_LOCK = threading.Lock()
_DEFS: Dict[str, Any] = {}
_VALUES: Dict[str, Any] = {}


def define_flag(name: str, default: Any, help_str: str = "") -> None:
    with _LOCK:
        if name in _DEFS:
            return
        _DEFS[name] = (default, help_str)
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            _VALUES[name] = _coerce(env, default)
        else:
            _VALUES[name] = default


def _coerce(text: str, default: Any) -> Any:
    if isinstance(default, bool):
        return text.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(text)
    if isinstance(default, float):
        return float(text)
    return text


def get_flags(name: str) -> Any:
    with _LOCK:
        if name not in _VALUES:
            raise KeyError(f"undefined flag: {name}")
        return _VALUES[name]


def set_flags(flags: Dict[str, Any]) -> None:
    with _LOCK:
        for k, v in flags.items():
            if k not in _DEFS:
                raise KeyError(f"undefined flag: {k}")
            _VALUES[k] = v


def all_flags() -> Dict[str, Any]:
    with _LOCK:
        return dict(_VALUES)


# ---------------------------------------------------------------------------
# Core flag set (parity with the PaddleBox block, flags.cc:946-975, plus
# TPU-specific knobs).
# ---------------------------------------------------------------------------
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("enable_pullpush_dedup_keys", True,
            "dedup minibatch keys before pull/push (flags.cc:946)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("enable_pull_box_padding_zero", True,
            "key 0 pulls a zero embedding (flags.cc:950)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("record_pool_max_size", 2_000_000,
            "SlotRecord arena cap (flags.cc:956 padbox_record_pool_max_size)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("dataset_shuffle_thread_num", 20,
            "global-shuffle sender threads (flags.cc:966)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("dataset_merge_thread_num", 20,
            "shuffle-receiver merge threads (flags.cc:968)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("auc_runner_mode", False,
            "enable AucRunner slot-replacement eval (flags.cc:972)")
define_flag("check_nan_inf", False,
            "per-batch NaN/Inf scan of model outputs (boxps_worker.cc:1326)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("feed_pass_thread_num", 8,
            "threads used to extract pass feasigns (box_wrapper.h:873 uses 30)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("pass_build_chunk", 500_000,
            "host->device pass-build chunk size (ps_gpu_wrapper.cc:757)")
# pboxlint: disable-next=PB205 -- paper-fidelity registry entry (PaddleBox parity), not yet wired
define_flag("tpu_batch_key_capacity", 0,
            "static per-batch key capacity; 0 = derive from data feed config")
define_flag("sharded_exchange_bf16", False,
            "move the mxu_sharded exchange's VALUE traffic (pull "
            "psum_scatter + push payload all_gather) in bfloat16 — halves "
            "ICI bytes at ~1e-2 relative error (EQuARX-style reduced-"
            "precision collectives; ids/plans stay exact).  Read at step-BUILD "
            "time: the packed loop retraces on a change, but a live "
            "streaming step keeps its compiled value")
define_flag("mxu_crossing", "auto",
            "sorted<->canonical crossing lowering for the mxu sparse path: "
            "take | sort | auto (auto = time both once per geometry on the "
            "live backend; ops/crossing.py)")
define_flag("ps_device_cache", False,
            "keep the hottest embedding rows resident in device memory "
            "across passes (the HBM tier of the HBM/DRAM/SSD store, "
            "≙ HeterPS fleet/heter_ps).  build_pull then fetches only "
            "cache MISSES over the wire; hits are gathered device-side "
            "into the pass working set.  Bit-identical to cache-off — "
            "the cache is write-back at pass granularity and never a "
            "second source of truth across a checkpoint commit")
define_flag("ps_device_cache_rows", 262_144,
            "row capacity of the device-resident hot-row cache "
            "(ps/device_cache.py); admission/eviction ranks by the "
            "day-scale delta_score stats plus pass recency")
define_flag("mxu_crossing_bf16", False,
            "move the mxu path's sorted<->canonical crossings in bfloat16 "
            "— halves the bytes of the dominant step cost (BENCH_r03: two "
            "~8.2ms crossings of a 34.6ms step) at ~4e-3 relative error on "
            "pulled values / push grads; the optimizer still accumulates "
            "f32.  Read at step-BUILD time, like sharded_exchange_bf16")
