"""Device-resident pass working set: pull (gather) and push (scatter-add).

TPU-native replacement for the HBM hash table + HeterComm all2all
(hashtable.h:114, heter_comm_inl.h:1117-1996) and the BoxWrapper pull/push
hot path (box_wrapper_impl.h:25-632, copy kernels box_wrapper.cu:75-600):

* key→row translation happens ON HOST at batch-pack time against the pass's
  sorted unique key array (PassKeyMapper below, ≙ DedupKeysAndFillIdx +
  build-pass dedup PreBuildTask ps_gpu_wrapper.cc:114) — so the device side
  is a pure dense-index gather/scatter that XLA tiles onto the MXU/HBM with
  no hash probes or dynamic shapes;
* cross-chip routing is GSPMD: the working set is row-sharded over the mesh
  (HybridTopology.table_spec) and jit-compiled gathers lower to the same
  all-to-all pattern HeterComm hand-codes.

Row 0 is the reserved zero row: padding positions and (optionally) key 0 pull
zeros and push nothing (≙ FLAGS_enable_pull_box_padding_zero).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from paddlebox_tpu.config import EmbeddingTableConfig

# Device pytree fields (all [N] except mf/mf_g2sum)
DEVICE_FIELDS = ("show", "click", "delta_score", "slot", "embed_w",
                 "embed_g2sum", "mf_size", "mf_g2sum", "mf")


def round_up(n: int, align: int) -> int:
    return ((n + align - 1) // align) * align


def size_bucket(n: int, align: int = 8) -> int:
    """Grow-only size buckets so per-pass working sets of similar size reuse
    the same compiled step (≙ DCacheBuffer grow-only realloc,
    box_wrapper.h:198)."""
    n = max(n, align)
    bucket = align
    while bucket < n:
        bucket *= 2
    # intermediate steps between powers of two cap padding waste at ~14%
    for frac in (5 * bucket // 8, 3 * bucket // 4, 7 * bucket // 8):
        if frac >= n and frac % align == 0:
            return frac
    return bucket


class PassKeyMapper:
    """Host-side key→pass-row translation over the sorted unique key array.

    Row 0 is reserved (zero row); real keys map to rows 1..n.  Above a size
    threshold the lookups run through the native open-addressing hash
    (native/hash_shard.cc — threaded, ~6x faster than np.searchsorted over
    a multi-MB key array); the numpy binary search remains the fallback.
    """

    _NATIVE_MIN = 65_536  # below this searchsorted wins (no build cost)

    def __init__(self, sorted_keys: np.ndarray):
        self.sorted_keys = sorted_keys  # unique, ascending, excludes 0
        self._native = None
        self._native_tried = False

    def _native_hash(self):
        if not self._native_tried:
            self._native_tried = True
            from paddlebox_tpu.native import build
            try:
                from paddlebox_tpu.native import hash_map
                if hash_map.available():
                    h = hash_map.NativeKeyHash(len(self.sorted_keys))
                    # insertion order == sorted order, so row i+1 matches
                    # the searchsorted contract exactly
                    h.upsert(self.sorted_keys)
                    self._native = h
            except Exception as e:  # noqa: BLE001 — searchsorted serves
                build.warn_fallback("pass_key_hash", e)
                self._native = None
        return self._native

    def __call__(self, keys: np.ndarray) -> np.ndarray:
        if len(self.sorted_keys) == 0:
            return np.zeros(len(keys), np.int32)
        if len(keys) >= self._NATIVE_MIN and len(self.sorted_keys) >= 1024:
            h = self._native_hash()
            if h is not None:
                return h.find_rows1_i32(np.asarray(keys, np.uint64))
        pos = np.searchsorted(self.sorted_keys, keys)
        pos_c = np.minimum(pos, len(self.sorted_keys) - 1)
        found = self.sorted_keys[pos_c] == keys
        return np.where(found, pos_c + 1, 0).astype(np.int32)

    @property
    def num_keys(self) -> int:
        return len(self.sorted_keys)


def build_working_set(host_soa: Dict[str, np.ndarray], mf_dim: int,
                      pad_to: Optional[int] = None,
                      sharding=None,
                      buffers: Optional[Dict[str, np.ndarray]] = None
                      ) -> Dict[str, jnp.ndarray]:
    """Assemble the device pytree from host rows (row 0 = zeros) and place it
    with the given NamedSharding (row-sharded over the mesh).

    The reserved all-zero row 0 is load-bearing for every step path:
    fast/mxu point padding occurrences at it so they pool as exact 0.0.

    ≙ BuildGPUTask's HBM pool fill (ps_gpu_wrapper.cc:684-760) — a single
    chunked H2D per field instead of 500k-key memcpy loops.

    ``buffers``, if given, is a caller-owned staging-buffer pool keyed by
    field: when the bucketed size is unchanged from the previous pass the
    padded host array is reused instead of reallocated (only the reserved
    row and the stale tail are re-zeroed; metered as
    ``ps.engine.ws_buffer_reuse``).  Reused staging is always *copied* to
    the device (never aliased) so mutating the buffer next pass cannot
    corrupt a live working set.
    """
    from paddlebox_tpu.utils.monitor import stat_add
    n = len(host_soa["show"])
    total = (pad_to if pad_to is not None else size_bucket(n + 1))
    assert total >= n + 1
    ws = {}
    reused = 0
    for f in host_soa:
        if f == "unseen_days":  # host-only lifecycle field
            continue
        src = host_soa[f]
        shape = (total,) + src.shape[1:]
        arr = None
        if buffers is not None:
            prev = buffers.get(f)
            if prev is not None and prev.shape == shape \
                    and prev.dtype == src.dtype:
                arr = prev
                arr[0] = 0          # reserved zero row
                arr[n + 1:] = 0     # stale rows from a larger prior pass
                reused += 1
        if arr is None:
            arr = np.zeros(shape, src.dtype)
            if buffers is not None:
                buffers[f] = arr
        arr[1:n + 1] = src
        dtype = jnp.int32 if src.dtype == np.int32 else jnp.float32
        if sharding is not None:
            ws[f] = jax.device_put(arr.astype(dtype), sharding)
        elif buffers is not None:
            # the staging buffer outlives this pass — force a device copy
            ws[f] = jnp.array(arr, dtype=dtype, copy=True)
        else:
            ws[f] = jnp.asarray(arr, dtype=dtype)
    if reused:
        stat_add("ps.engine.ws_buffer_reuse", float(reused))
    return ws


def scatter_device_rows(ws: Dict[str, jnp.ndarray], rows,
                        values: Dict[str, jnp.ndarray]
                        ) -> Dict[str, jnp.ndarray]:
    """Cached-plane working-set fill: scatter already-device-resident row
    values (a DeviceRowCache gather) into the pass working set — no host
    staging and no H2D for these rows.  Dtypes must already match the
    working set's (the cache stores build_working_set's exact casts), so
    ``pull_sparse``/``push_sparse_grads`` see bits identical to a wire
    pull.  Returns the updated pytree (functional, like every ws op)."""
    rows_d = jnp.asarray(rows)
    for f, v in values.items():
        if f in ws:
            ws[f] = ws[f].at[rows_d].set(v)
    return ws


def dump_working_set(ws: Dict[str, jnp.ndarray], n: int
                     ) -> Dict[str, np.ndarray]:
    """Device→host for end_pass write-back (≙ dump_pool_to_cpu_func,
    ps_gpu_wrapper.cc:983+ / accessor DumpFill).  Table-wide scalars
    (e.g. a serving freeze's mf_scale) are not row data and are skipped."""
    return {f: np.asarray(ws[f])[1:n + 1] for f in ws
            if getattr(ws[f], "ndim", 1) >= 1}


def quantize_working_set(ws: Dict[str, jnp.ndarray], quant_bits: int = 16,
                         scale: float = 1.0 / 32767.0
                         ) -> Dict[str, jnp.ndarray]:
    """Serving-mode freeze: re-encode mf as int16 grid points so embedx
    pulls read half the HBM bytes and the table holds half the memory
    (≙ the quant feature value + EmbedxQuantOp dequant-on-pull,
    box_wrapper.cu:37-44, table-wide pull_embedx_scale box_wrapper.h:655).

    The quantized working set is PULL-ONLY — pushes require the f32 store
    (the reference likewise quantizes only dumped/serving tables)."""
    if quant_bits != 16:
        raise ValueError("only quant_bits=16 (int16 grid) is supported")
    out = dict(ws)
    q = jnp.clip(jnp.round(ws["mf"] / scale), -32767, 32767)
    out["mf"] = q.astype(jnp.int16)
    out["mf_scale"] = jnp.float32(scale)
    return out


def mf_values(ws: Dict[str, jnp.ndarray], gathered: jnp.ndarray
              ) -> jnp.ndarray:
    """Dequantize gathered mf rows when the working set is frozen int16
    (EmbedxQuantOp: dest = int16 * scale); identity for the f32 store."""
    if jnp.issubdtype(gathered.dtype, jnp.integer) and "mf_scale" in ws:
        return gathered.astype(jnp.float32) * ws["mf_scale"]
    return gathered


def is_quantized(ws: Dict[str, jnp.ndarray]) -> bool:
    return "mf_scale" in ws


def pull_sparse(ws: Dict[str, jnp.ndarray], indices: jnp.ndarray
                ) -> jnp.ndarray:
    """Gather pull values [*, 3+D]: (show, click, embed_w, embedx×D).

    ≙ PullSparseCaseGPU + CopyForPull (box_wrapper_impl.h:25,
    box_wrapper.cu:945).  mf is masked until created (mf_size>0 —
    CommonPullValue semantics, feature_value.h:161); a serving-frozen
    int16 table dequantizes after the gather (half the gather bytes,
    ≙ EmbedxQuantOp).
    """
    show = ws["show"][indices]
    click = ws["click"][indices]
    embed_w = ws["embed_w"][indices]
    created = (ws["mf_size"][indices] > 0).astype(jnp.float32)
    mf = mf_values(ws, ws["mf"][indices]) * created[..., None]
    return jnp.concatenate(
        [show[..., None], click[..., None], embed_w[..., None], mf], axis=-1)


def pull_sparse_extended(ws: Dict[str, jnp.ndarray], indices: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """≙ pull_box_extended_sparse / PullCopyNNCross (box_wrapper.cu:147):
    base pull value plus the expand ("NNCross") embedding, gated by the same
    mf-created mask."""
    base = pull_sparse(ws, indices)
    created = (ws["mf_size"][indices] > 0).astype(ws["mf_ex"].dtype)
    emb_ex = ws["mf_ex"][indices] * created[..., None]
    return base, emb_ex


def push_sparse_grads(ws: Dict[str, jnp.ndarray], indices: jnp.ndarray,
                      grads: jnp.ndarray, slot_ids: jnp.ndarray
                      ) -> Dict[str, jnp.ndarray]:
    """Accumulate per-row push values by scatter-add (merge-by-key,
    ≙ PushMergeCopyAtomic box_wrapper.cu:476 / dynamic_merge_grad).

    indices: [S,B,L] pass rows; grads: [S,B,L,3+D] where cols are
    (g_show, g_click, g_embed, g_embedx...); slot_ids: [S] int32.
    Returns accumulators dict with g_show/g_click/g_embed/g_embedx [N(,D)]
    and the per-row slot id.  Row 0 (padding) accumulates too but is ignored
    by the optimizer mask.
    """
    n = ws["show"].shape[0]
    flat_idx = indices.reshape(-1)
    flat_g = grads.reshape(-1, grads.shape[-1])
    S, B, L = indices.shape
    flat_slot = jnp.broadcast_to(
        slot_ids[:, None, None], (S, B, L)).reshape(-1)
    # padding / masked positions carry all-zero grads already (seqpool bwd
    # masks by key validity); zero their index to the reserved row anyway.
    zeros = jnp.zeros((n,), flat_g.dtype)
    acc = {
        "g_show": zeros.at[flat_idx].add(flat_g[:, 0]),
        "g_click": zeros.at[flat_idx].add(flat_g[:, 1]),
        "g_embed": zeros.at[flat_idx].add(flat_g[:, 2]),
        "g_embedx": jnp.zeros_like(ws["mf"]).at[flat_idx].add(flat_g[:, 3:]),
        # only valid occurrences vote (the show grad column carries the
        # seqpool key mask: ins_show > 0 exactly where the key is real)
        "slot": jnp.zeros((n,), jnp.int32).at[flat_idx].max(
            jnp.where(flat_g[:, 0] > 0, flat_slot.astype(jnp.int32), 0)),
    }
    return acc


def push_sparse_grads_extended(ws, indices, grads, grads_ex, slot_ids):
    """Extended push: base accumulators + expand-embedding grads
    (≙ push_box_extended_sparse)."""
    acc = push_sparse_grads(ws, indices, grads, slot_ids)
    flat_idx = indices.reshape(-1)
    flat_gx = grads_ex.reshape(-1, grads_ex.shape[-1])
    acc["g_embedx_ex"] = jnp.zeros_like(ws["mf_ex"]).at[flat_idx].add(flat_gx)
    return acc
