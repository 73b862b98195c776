"""Sorted one-hot-matmul gather/scatter — the TPU-native sparse hot path.

Why this exists (measured on v5e): XLA lowers `table[idx]` gathers and
`.at[idx].add` scatters to a *serial* per-row loop on TPU — ~5-10ms per
426k-row gather and ~36ms per 426k-row scatter into a [2M, 8] table.  The
reference's CUDA kernels (PullCopy box_wrapper.cu:75, PushMergeCopyAtomic
box_wrapper.cu:476, HeterComm merge heter_comm_inl.h:69-103) rely on massive
scatter/gather parallelism + atomics that the TPU memory system does not
offer.  The TPU-native formulation: treat pull/push as a block-sparse matrix
product and feed the MXU —

  1. sort the batch's row ids once (`lax.sort`, bitonic, vectorized, ~0.5ms);
  2. walk the sorted occurrences in fixed 512-wide *chunks* against 2048-row
     table *tiles*; each (chunk, tile) work item builds a {0,1} one-hot in
     VMEM and runs one [W,TILE]x[TILE,C] (gather) or [W,C]x[C,TILE] (scatter)
     matmul on the MXU — duplicates merge for free in the contraction;
  3. a worklist enumerates the (chunk, tile) pairs actually touched.  Because
     rows are sorted, each chunk's tiles are a consecutive range and every
     tile's visits are adjacent in the worklist, so Pallas block revisiting
     accumulates partial products in VMEM without ever materializing the
     one-hot in HBM (a pure-XLA scan of the same schedule spends ~8us/item
     on HBM one-hot traffic; the Pallas kernel spends ~2us on the MXU).

Skew-robust with *static* shapes: a popular key spanning many chunks just
contributes to more work items; the worklist bound is exactly
  n_chunks + n_tiles   (each chunk >= 1 item; tile-boundary crossings and
gap fills add at most one item per tile), so jit shapes never depend on the
key distribution.

All offsets are chunk-aligned, so every DMA is a regular [W, C]/[W, TILE]
block copy (no per-row DMAs — TPU DMA wants 128-lane-aligned slices).

Width: up to W_BLOCK feature rows a table/payload block holds all of W (the
CTR tables: W = 12, 36).  A wider table (a 2048-wide sequence-model row is
W = 2052: one [W, TILE] float32 block would be 16.8 MB, past VMEM) is cut
into W_BLOCK-row blocks along a leading grid axis; the one-hot does not
depend on it, so each block of rows walks the same worklist and keeps the
consecutive-revisit accumulation.  ``padded_width`` says at what height a
caller builds such a table or payload (``ps/mxu_path.py`` does): whole
blocks, no pad copy here.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 512     # occurrences per work-item (lane dim of payload blocks)
TILE = 2048     # table rows per tile (lane dim of table blocks)
W_BLOCK = 128   # feature rows per block once W outgrows one block
# stable kernel names: the Mosaic custom calls carry them (kernel_name), so
# a trace reduction or chip_smoke.py finds the kernels after a refactor
GATHER_KERNEL = "sorted_spmm_gather"
SCATTER_KERNEL = "sorted_spmm_scatter"


def _round_up(n: int, a: int) -> int:
    return (n + a - 1) // a * a


def padded_width(w: int) -> int:
    """Feature-major height the kernels run at: ``w`` itself while one
    block holds it, else ``w`` rounded up to whole W_BLOCK blocks."""
    return w if w <= W_BLOCK else _round_up(w, W_BLOCK)


@dataclasses.dataclass(frozen=True)
class SpmmDims:
    """Static geometry shared by the plan and both kernels."""
    p: int           # real occurrence count
    p_pad: int       # p rounded up to CHUNK
    n_chunks: int
    n_kernel: int    # table rows incl. the trailing sentinel tile
    n_tiles: int     # n_kernel // TILE
    n_work: int      # n_chunks + n_tiles (static worklist bound)
    chunk: int = CHUNK
    tile: int = TILE

    @property
    def sentinel(self) -> int:
        """Row id pad occurrences are parked at: first row of the last
        (sentinel) tile — gathers zeros, scatters into a discarded tile."""
        return self.n_kernel - self.tile


def spmm_dims(p: int, n_rows: int, chunk: int = CHUNK,
              tile: int = TILE) -> SpmmDims:
    """n_rows: logical table height (rows 0..n_rows-1 addressable)."""
    p_pad = _round_up(max(p, 1), chunk)
    n_kernel = _round_up(n_rows, tile) + tile  # + sentinel tile
    n_tiles = n_kernel // tile
    n_chunks = p_pad // chunk
    return SpmmDims(p=p, p_pad=p_pad, n_chunks=n_chunks, n_kernel=n_kernel,
                    n_tiles=n_tiles, n_work=n_chunks + n_tiles,
                    chunk=chunk, tile=tile)


def with_p_pad(dims: SpmmDims, p_pad: int) -> SpmmDims:
    """The same table geometry over a different (chunk-aligned) sorted-
    domain width — single source of the n_work = n_chunks + n_tiles
    worklist invariant for trimmed plans."""
    n_chunks = p_pad // dims.chunk
    return dataclasses.replace(dims, p=p_pad, p_pad=p_pad, n_chunks=n_chunks,
                               n_work=n_chunks + dims.n_tiles)


def trimmed_dims(dims: SpmmDims, max_real: int) -> SpmmDims:
    """Static geometry for a plan that drops leading padding occurrences.

    Padding/unseen occurrences carry row 0 and therefore sort to the FRONT
    of the sorted domain; keeping only the last `keep` sorted positions
    (chunk-aligned, `keep >= max_real + sentinel tail`) still covers every
    real occurrence.  At avg_len < capacity this shrinks the kernel
    worklist and the push crossing by the padding fraction (the reference
    never materializes padding at all — its pack is LoD-ragged,
    data_feed.cu:1210; this is the static-shape equivalent).

    The kept width is bucketed to 1/8ths of the full width so passes whose
    widest batch drifts between builds land on at most 8 distinct plan
    shapes — a new shape retraces the packed step jit, and an unbounded
    per-pass recompile would cost far more than the trim saves.
    """
    tail = dims.p_pad - dims.p          # sentinel-padded tail, always kept
    keep = _round_up(min(dims.p_pad, max(max_real + tail, 1)), dims.chunk)
    granule = _round_up(max(dims.p_pad // 8, dims.chunk), dims.chunk)
    keep = min(_round_up(keep, granule), dims.p_pad)
    return with_p_pad(dims, keep)


def build_plan(rows: jnp.ndarray, dims: SpmmDims, eff: SpmmDims = None):
    """Sort the occurrence row ids and enumerate (chunk, tile) work items.

    rows: [p] int32 in canonical (slot, lod, batch) order.
    Returns (rows2d [n_chunks, chunk] sorted+padded, perm [p], inv_perm [p],
    chunk_ids [n_work], tile_ids [n_work], first_gather [n_work],
    first_scatter [n_work], first_occ [p_pad]).  first_occ marks the first
    occurrence of each distinct row in sorted order — lets a scatter carry an
    exact "any one occurrence" column (e.g. the slot id) instead of a mean.
    Everything vectorized — no serial scatters.

    eff (from `trimmed_dims`): emit the trimmed plan instead — the sorted
    arrays keep only the last eff.p_pad positions (callers guarantee the
    dropped prefix is all row-0 occurrences, i.e. the number of nonzero
    rows is <= eff.p_pad - (dims.p_pad - dims.p)).  Shape changes:
    rows2d [eff.n_chunks, chunk] and the worklist shrink; perm stays the
    FULL [p] bijection (sorted position -> canonical source, position 0 =
    first DROPPED element — consumers derive the kept suffix with a static
    slice, see mxu_path); inv_perm [p] becomes the kept-domain position,
    NEGATIVE for dropped (row-0) occurrences — gather consumers mask those
    to zero, exactly the value row 0 holds.
    """
    p, c, t = dims.p, dims.chunk, dims.tile
    iota = jnp.arange(p, dtype=jnp.int32)
    sorted_rows, perm = jax.lax.sort((rows.astype(jnp.int32), iota),
                                     num_keys=1)
    inv_perm = jax.lax.sort((perm, iota), num_keys=1)[1]
    pad = jnp.full((dims.p_pad - p,), dims.sentinel, jnp.int32)
    rows_padded = jnp.concatenate([sorted_rows, pad])
    if eff is not None and eff.p_pad < dims.p_pad:
        p0 = dims.p_pad - eff.p_pad     # static, chunk-aligned
        rows_padded = rows_padded[p0:]
        inv_perm = inv_perm - p0
        dims = eff
    first_occ = jnp.concatenate(
        [jnp.ones((1,), jnp.float32),
         (rows_padded[1:] != rows_padded[:-1]).astype(jnp.float32)])
    rows2d = rows_padded.reshape(dims.n_chunks, 1, c)

    tile_of = rows2d[:, 0, :] // t                          # [n_chunks, c]
    lo, hi = tile_of[:, 0], tile_of[:, -1]
    # visit range per chunk: cover inter-chunk tile gaps (so every tile is
    # visited exactly once overall — scatter needs zero-filled deltas) and
    # share boundary tiles (consecutive visits => VMEM accumulation works)
    vlo = jnp.concatenate([jnp.zeros((1,), lo.dtype),
                           jnp.minimum(lo[1:], hi[:-1] + 1)])
    vhi = jnp.concatenate([hi[:-1], jnp.full((1,), dims.n_tiles - 1,
                                             hi.dtype)])
    slots = vhi - vlo + 1                                   # >= 1
    cum = jnp.cumsum(slots)
    work = jnp.arange(dims.n_work, dtype=jnp.int32)
    c_of = jnp.searchsorted(cum, work, side="right").astype(jnp.int32)
    c_of = jnp.minimum(c_of, dims.n_chunks - 1)
    base = jnp.where(c_of > 0, cum[jnp.maximum(c_of - 1, 0)], 0)
    tile_ids = jnp.clip(vlo[c_of] + work - base, 0, dims.n_tiles - 1)
    tile_ids = tile_ids.astype(jnp.int32)
    first_g = jnp.concatenate([jnp.ones((1,), jnp.int32),
                               (c_of[1:] != c_of[:-1]).astype(jnp.int32)])
    first_s = jnp.concatenate([jnp.ones((1,), jnp.int32),
                               (tile_ids[1:] != tile_ids[:-1]).astype(
                                   jnp.int32)])
    return rows2d, perm, inv_perm, c_of, tile_ids, first_g, first_s, first_occ


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _gather_kernel(ch_ref, tl_ref, fst_ref, rows_ref, table_ref, out_ref,
                   work_axis: int = 0):
    i = pl.program_id(work_axis)
    tile = tl_ref[i]
    t = table_ref.shape[1]
    c = rows_ref.shape[2]
    loc = rows_ref[0, 0, :] - tile * t                     # [c]
    oh = (jax.lax.broadcasted_iota(jnp.int32, (t, c), 0)
          == loc[None, :]).astype(jnp.bfloat16)            # [t, c] in VMEM
    # one-hot entries are exact in bf16, so a hi/lo split of the f32 table
    # gives f32-accurate sums in two cheap bf16 MXU passes (vs 6 for
    # Precision.HIGHEST)
    tab = table_ref[...]
    hi = tab.astype(jnp.bfloat16)
    lo = (tab - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dn = (((1,), (0,)), ((), ()))
    contrib = (jax.lax.dot_general(hi, oh, dn,
                                   preferred_element_type=jnp.float32)
               + jax.lax.dot_general(lo, oh, dn,
                                     preferred_element_type=jnp.float32))

    @pl.when(fst_ref[i] == 1)
    def _():
        out_ref[...] = contrib

    @pl.when(fst_ref[i] == 0)
    def _():
        out_ref[...] += contrib


def _scatter_kernel(ch_ref, tl_ref, fst_ref, rows_ref, pay_ref, out_ref,
                    work_axis: int = 0):
    i = pl.program_id(work_axis)
    tile = tl_ref[i]
    t = out_ref.shape[1]
    c = rows_ref.shape[2]
    loc = rows_ref[0, 0, :] - tile * t                     # [c]
    oh = (loc[:, None] ==
          jax.lax.broadcasted_iota(jnp.int32, (c, t), 1)
          ).astype(jnp.bfloat16)                           # [c, t] in VMEM
    pay = pay_ref[...]
    hi = pay.astype(jnp.bfloat16)
    lo = (pay - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dn = (((1,), (0,)), ((), ()))
    contrib = (jax.lax.dot_general(hi, oh, dn,
                                   preferred_element_type=jnp.float32)
               + jax.lax.dot_general(lo, oh, dn,
                                     preferred_element_type=jnp.float32))

    @pl.when(fst_ref[i] == 1)
    def _():
        out_ref[...] = contrib

    @pl.when(fst_ref[i] == 0)
    def _():
        out_ref[...] += contrib


def _call_blocked(kernel, name: str, scalars, rows2d, operand, dims,
                  gather: bool, interpret: bool) -> jnp.ndarray:
    """The wide form of both kernels: grid (W blocks, worklist).  The
    worklist axis is the inner one, so an output block's revisits stay
    consecutive within a block of rows.  The gather reads table tiles and
    writes occurrence chunks, the scatter the reverse."""
    w = operand.shape[0]
    if w % W_BLOCK:
        raise ValueError(
            f"a table or payload of {w} feature rows is built at "
            f"padded_width({w}) = {padded_width(w)}: whole blocks of rows")
    c, t = dims.chunk, dims.tile

    def by_tile(j, i, ch, tl, fs):
        return (j, tl[i])

    def by_chunk(j, i, ch, tl, fs):
        return (j, ch[i])

    tiles = pl.BlockSpec((W_BLOCK, t), by_tile)
    chunks = pl.BlockSpec((W_BLOCK, c), by_chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(w // W_BLOCK, dims.n_work),
        in_specs=[
            pl.BlockSpec((1, 1, c), lambda j, i, ch, tl, fs: (ch[i], 0, 0)),
            tiles if gather else chunks,
        ],
        out_specs=chunks if gather else tiles,
    )
    return pl.pallas_call(
        functools.partial(kernel, work_axis=1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (w, dims.p_pad if gather else dims.n_kernel), jnp.float32),
        interpret=interpret,
        name=name,
    )(*scalars, rows2d, operand)


def gather_sorted(table_fm: jnp.ndarray, rows2d: jnp.ndarray,
                  chunk_ids: jnp.ndarray, tile_ids: jnp.ndarray,
                  first_g: jnp.ndarray, dims: SpmmDims,
                  interpret: bool = False) -> jnp.ndarray:
    """table_fm [W, n_kernel] feature-major -> gathered [W, p_pad] in sorted
    occurrence order (pad columns come from the zero sentinel tile)."""
    w = table_fm.shape[0]
    c, t = dims.chunk, dims.tile
    if w > W_BLOCK:
        return _call_blocked(_gather_kernel, GATHER_KERNEL,
                             (chunk_ids, tile_ids, first_g), rows2d, table_fm,
                             dims, True, interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(dims.n_work,),
        in_specs=[
            pl.BlockSpec((1, 1, c), lambda i, ch, tl, fs: (ch[i], 0, 0)),
            pl.BlockSpec((w, t), lambda i, ch, tl, fs: (0, tl[i])),
        ],
        out_specs=pl.BlockSpec((w, c), lambda i, ch, tl, fs: (0, ch[i])),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, dims.p_pad), jnp.float32),
        interpret=interpret,
        name=GATHER_KERNEL,
    )(chunk_ids, tile_ids, first_g, rows2d, table_fm)


def scatter_add_sorted(payload_fm: jnp.ndarray, rows2d: jnp.ndarray,
                       chunk_ids: jnp.ndarray, tile_ids: jnp.ndarray,
                       first_s: jnp.ndarray, dims: SpmmDims,
                       interpret: bool = False) -> jnp.ndarray:
    """payload_fm [W, p_pad] in sorted order -> merged delta [W, n_kernel]
    (every table row = sum of its occurrences' payload columns; untouched
    rows exactly zero; sentinel tile holds pad garbage — slice it off)."""
    w = payload_fm.shape[0]
    c, t = dims.chunk, dims.tile
    if w > W_BLOCK:
        return _call_blocked(_scatter_kernel, SCATTER_KERNEL,
                             (chunk_ids, tile_ids, first_s), rows2d,
                             payload_fm, dims, False, interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(dims.n_work,),
        in_specs=[
            pl.BlockSpec((1, 1, c), lambda i, ch, tl, fs: (ch[i], 0, 0)),
            pl.BlockSpec((w, c), lambda i, ch, tl, fs: (0, ch[i])),
        ],
        out_specs=pl.BlockSpec((w, t), lambda i, ch, tl, fs: (0, tl[i])),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, dims.n_kernel), jnp.float32),
        interpret=interpret,
        name=SCATTER_KERNEL,
    )(chunk_ids, tile_ids, first_s, rows2d, payload_fm)


# ---------------------------------------------------------------------------
# XLA reference implementations (CPU tests / fallback)
# ---------------------------------------------------------------------------

def gather_sorted_xla(table_fm, rows2d, chunk_ids, tile_ids, first_g, dims,
                      interpret: bool = False):
    rows = rows2d.reshape(-1)
    return jnp.take(table_fm, rows, axis=1)


def scatter_add_sorted_xla(payload_fm, rows2d, chunk_ids, tile_ids, first_s,
                           dims, interpret: bool = False):
    rows = rows2d.reshape(-1)
    out = jnp.zeros((payload_fm.shape[0], dims.n_kernel), jnp.float32)
    return out.at[:, rows].add(payload_fm)
