"""Permutation crossings between sorted and canonical occurrence domains.

The mxu hot path (ps/mxu_path.py) moves per-occurrence values between
canonical [S, L, B] order and the plan's sorted order twice per step.
BENCH_r03's step profile measured these two crossings as the DOMINANT step
cost (~8.2 ms each at 1.27M x 12 f32 on v5e): XLA lowers `jnp.take` to a
serial per-row gather on TPU.  Two interchangeable lowerings:

* "take" — jnp.take rows by source index (current XLA gather).
* "sort" — applying a known permutation IS a key-value sort whose keys are
  the DESTINATION positions: `lax.sort((dest, v0, ..., vw))` lands value j
  at position dest[j], and XLA's TPU sort is a vectorized bitonic network,
  not a serial gather.  (The reference never faces this: CUDA scatters by
  thread id, box_wrapper.cu:75; the sort IS the TPU-native scatter.)

Which wins depends on backend and geometry, so `best_mode` measures both
once per geometry on the live backend and caches the answer
(FLAGS_mxu_crossing pins it to "take"/"sort" explicitly).
"""

from __future__ import annotations

import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu import flags
from paddlebox_tpu.utils.monitor import stat_observe

log = logging.getLogger(__name__)


def permute_by_dest(channels, dest: jnp.ndarray):
    """out[:, dest[j]] = values[:, j] for a permutation `dest` of 0..n-1.

    channels: sequence of [n] arrays (channel-major payload).  Returns the
    permuted channels stacked [w, n].  Lowered as ONE multi-operand sort.
    """
    ops = jax.lax.sort((dest,) + tuple(channels), num_keys=1)
    return jnp.stack(ops[1:], axis=0)


def _bench_once(fn, args, reps: int = 3) -> float:
    r = jax.jit(fn)
    out = r(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = r(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def best_mode(take_rows: int, sort_n: int, w: int, backend: str,
              dtype: str = "float32") -> str:
    """Measured winner for a crossing that a "take" lowering serves with
    `take_rows` output rows and a "sort" lowering serves with a `sort_n`-
    element w+1-operand sort.  Measurements cached per geometry (including
    the crossing dtype — bf16 halves the bytes and shifts the take/sort
    break-even); the flag is read OUTSIDE the cache so pinning works after
    a tuned pass too."""
    mode = flags.get_flags("mxu_crossing")
    if mode not in ("take", "sort", "auto"):
        raise ValueError(
            f"FLAGS_mxu_crossing={mode!r}: must be take | sort | auto")
    if mode != "auto":
        return mode
    if backend == "cpu":
        return "take"       # XLA CPU gathers are fine; sort is the slow one
    return _measure(take_rows, sort_n, w, backend, dtype)


@functools.lru_cache(maxsize=None)
def _measure(take_rows: int, sort_n: int, w: int, backend: str,
             dtype: str = "float32") -> str:
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.normal(0, 1, (sort_n, w)).astype(
        np.float32)).astype(dtype)
    idx = jnp.asarray(
        rng.integers(0, sort_n, take_rows).astype(np.int32))
    dest = jnp.asarray(rng.permutation(sort_n).astype(np.int32))
    # NOTE (PR 31): this times a row-major `take` of a [sort_n, w] source.
    # The step's pull crossing gathers feature-major, or row-major at lane
    # width where mxu_path.cross_lane_width says so, and its cost hangs on
    # whether the source fits fast memory, so this is not the take a wide
    # pull runs (ROADMAP queue 3 `crossing-autotune`; the cells pin take).
    # both lowerings must compile on the live backend: a failure here is
    # a finding about the device, so it propagates (a silent "take" would
    # hide it behind the slower crossing)
    t_take = _bench_once(lambda v, i: jnp.take(v, i, axis=0), (src, idx))
    t_sort = _bench_once(
        lambda v, d: permute_by_dest(tuple(v.T), d), (src, dest))
    mode = "sort" if t_sort < t_take else "take"
    # two compiles + timed runs per geometry, paid at step-build time
    stat_observe("ops.crossing.autotune_s", time.perf_counter() - t0)
    log.info("crossing auto-tune (take_rows=%d sort_n=%d w=%d %s): "
             "take=%.2fms sort=%.2fms -> %s", take_rows, sort_n, w, backend,
             t_take * 1e3, t_sort * 1e3, mode)
    return mode
