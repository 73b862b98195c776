"""MXU sparse step path: pull/pool and push/update via sorted_spmm kernels.

Third-generation hot path (v1 `embedding.py` gathers → v2 `fast_path.py`
tiling-aware scatters → v3 this): the per-batch embedding traffic runs
through the sorted one-hot-matmul kernels (ops/sorted_spmm.py), which turn
TPU's serial gather/scatter into MXU block-sparse matmuls.  The optimizer
is the unchanged full-table `ps.optimizer.apply_push` — the scatter kernel
materializes the same merged per-row accumulators (`g_show`, `g_click`,
`g_embed`, `g_embedx`, slot) the v1 path built with
`.at[].add`, so every optimizer rule (adagrad / shared_adam / naive) works
and semantics match optimizer.cuh.h exactly (up to f32 summation order;
the kernels' hi/lo bf16 split carries ~1e-5 relative error).

≙ reference hot path: PullSparseCaseGPU + CopyForPull
(box_wrapper_impl.h:25, box_wrapper.cu:945), PushMergeCopy merge-by-key
(box_wrapper.cu:417), HashTable::update (hashtable_kernel.cu).

Layout notes: occurrence order is canonical [S, L, B] flattened; the plan's
`perm`/`inv_perm` move between canonical and sorted domains (one XLA row
gather each way, the only serial-ish ops left; on a v5e the pull's is 1.9 ms
at DeepFM's 426k 11-wide rows and, since PR 31, ~14 ms at Wide&Deep's 950k
35-wide ones, PERF.md §5).  The pull table is feature-major [W, n_kernel]
with W = 3 + D (+ Dex) + 1 (rows: show, click, embed_w, mf×D, optional
expand mf_ex×Dex, mf_size) so kernel blocks tile perfectly and the build is
W row writes, not an [N, D] relayout; past sorted_spmm.W_BLOCK rows (a
2048-wide sequence row) it is built at the kernels' padded height.  The
kernels' output stays feature-major too; the "take" pull crossing alone may
leave that layout: where its feature-major source would not fit the chip's
fast memory it relayouts the sorted columns once a step into row-major rows
padded to the lane width and gathers those (`cross_lane_width`, the rule and
its chip readings below).

Two consumers of the canonical values: the pooled CTR towers sum a slot's
rows over its capacity (`pull_pool_cvm`, and `d_pooled` is broadcast back
over L in the push; [S, L, B] is storage at the widest slot's capacity, and
the pull crossing takes only the positions each slot's own declared
capacity can hold, one take a group of slots of equal capacity); a model that takes its rows unpooled (a sequence
tower, `models/looplm.py`) reads `pull_rows` [S, L, B, 3 + D] as they are
and hands `push_and_update` a gradient per occurrence (`d_occ`), merged by
key like any other.
"""

from __future__ import annotations

import itertools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import SparseSGDConfig
from paddlebox_tpu.ops import sorted_spmm as sp
from paddlebox_tpu.ps import optimizer as sparse_opt
from paddlebox_tpu.utils import trace


def make_dims(num_occurrences: int, num_rows: int) -> sp.SpmmDims:
    return sp.spmm_dims(num_occurrences, num_rows)


def build_plan(idx_slb: jnp.ndarray, dims: sp.SpmmDims,
               eff: sp.SpmmDims = None):
    """idx_slb [S, L, B] pass rows (0 = reserved/padding row)."""
    return sp.build_plan(idx_slb.reshape(-1), dims, eff)


def plan_eff_dims(plan, dims: sp.SpmmDims) -> Optional[sp.SpmmDims]:
    """Trimmed kernel geometry a plan was built with, recovered from its
    static array shapes (None = untrimmed) — so consumers need no side
    channel and jit retraces correctly when the trim width changes."""
    n_chunks = plan[0].shape[0]
    if n_chunks == dims.n_chunks:
        return None
    return sp.with_p_pad(dims, n_chunks * dims.chunk)


def _ex_dim(ws: Dict[str, jnp.ndarray]) -> int:
    """Expand ("NNCross") embedding width, 0 without one — the ex columns
    ride the same feature-major table/payload directly after mf, so the
    kernels (any width: one block of rows up to sorted_spmm.W_BLOCK,
    W_BLOCK-row blocks beyond it) and the pooling (everything between col
    3 and the trailing mf_size is an embedding masked by created) need no
    branches."""
    return ws["mf_ex"].shape[1] if "mf_ex" in ws else 0


def _pull_table(ws: Dict[str, jnp.ndarray], dims: sp.SpmmDims) -> jnp.ndarray:
    """Feature-major pull view [3 + D (+ Dex) + 1, n_kernel]; a table
    wider than one kernel block is built at the kernels' padded height
    (zero rows below mf_size), so that they make no pad copy."""
    from paddlebox_tpu.ps.embedding import mf_values
    n = ws["show"].shape[0]
    d = ws["mf"].shape[1]
    dx = _ex_dim(ws)
    with trace.device_scope("ps.pull.table"):
        tab = jnp.zeros((sp.padded_width(3 + d + dx + 1), dims.n_kernel),
                        jnp.float32)
        tab = tab.at[0, :n].set(ws["show"])
        tab = tab.at[1, :n].set(ws["click"])
        tab = tab.at[2, :n].set(ws["embed_w"])
        # pboxlint: disable-next=PB301 -- documented pull-table build cost (one relayout per step, not per-row math)
        tab = tab.at[3:3 + d, :n].set(mf_values(ws, ws["mf"]).T)
        if dx:
            # pboxlint: disable-next=PB301 -- documented pull-table build cost (one relayout per step, not per-row math)
            tab = tab.at[3 + d:3 + d + dx, :n].set(ws["mf_ex"].T)
        # pboxlint: disable-next=PB301 -- documented pull-table build cost (one relayout per step, not per-row math)
        tab = tab.at[3 + d + dx, :n].set(ws["mf_size"].astype(jnp.float32))
    return tab


def pool_cvm_values(v: jnp.ndarray, use_cvm: bool = True,
                    premasked: bool = False) -> jnp.ndarray:
    """Canonical per-occurrence pull values [S, L, B, 3+D+1] (last col =
    mf_size) → pooled [B, S, 3+D].  Shared by the single-chip path and the
    shard_map'd multi-chip step (which pools its LOCAL batch shard).

    premasked: v is [S, L, B, 3+D] with the created mask already applied
    to the mf columns (the mxu path does this in the SORTED domain so the
    mf_size column never rides the crossing)."""
    d = v.shape[-1] - (3 if premasked else 4)
    with trace.device_scope("ps.pull.pool"):
        mf = v[..., 3:3 + d]
        if not premasked:
            mf = mf * (v[..., 3 + d:] > 0).astype(v.dtype)  # [S,L,B,1] mask
        show = jnp.sum(v[..., 0], axis=1)                   # [S, B]
        click = jnp.sum(v[..., 1], axis=1)
        w = jnp.sum(v[..., 2], axis=1)
        mf = jnp.sum(mf, axis=1)                            # [S, B, D]
        if use_cvm:
            show_t = jnp.log(show + 1.0)
            click_t = jnp.log(click + 1.0) - show_t
        else:
            show_t, click_t = show, click
        head = jnp.stack([show_t, click_t, w], axis=-1)     # [S, B, 3]
        pooled = jnp.concatenate([head, mf], axis=-1)
        return jnp.transpose(pooled, (1, 0, 2))             # [B, S, E]


def push_payload(d_pooled: jnp.ndarray, ins_cvm: jnp.ndarray,
                 slot_ids: jnp.ndarray,
                 shape_slb: Tuple[int, int, int]) -> jnp.ndarray:
    """Canonical per-occurrence push payload [S, L, B, D+4]:
    g_show, g_click, g_embed, g_mf x D, slot (reference push semantics —
    cols 0,1 of d_pooled are ignored, replaced by the instance cvm,
    box_wrapper_impl.h:373)."""
    s, l, b = shape_slb
    d = d_pooled.shape[-1] - 3
    g_show = jnp.broadcast_to(ins_cvm[None, None, :, 0], (s, l, b))
    g_click = jnp.broadcast_to(ins_cvm[None, None, :, 1], (s, l, b))
    d_w = jnp.transpose(d_pooled[:, :, 2], (1, 0))         # [S, B]
    g_embed = jnp.broadcast_to(d_w[:, None, :], (s, l, b))
    d_mf = jnp.transpose(d_pooled[:, :, 3:], (1, 0, 2))    # [S, B, D]
    g_mf = jnp.broadcast_to(d_mf[:, None], (s, l, b, d))
    slot_col = jnp.broadcast_to(
        slot_ids.astype(jnp.float32)[:, None, None], (s, l, b))
    return jnp.concatenate(
        [jnp.stack([g_show, g_click, g_embed], axis=-1), g_mf,
         slot_col[..., None]], axis=-1)                    # [S,L,B,D+4]


def acc_from_delta(delta: jnp.ndarray, n: int,
                   d_main: int = None) -> Dict[str, jnp.ndarray]:
    """Merged per-row accumulators for ps.optimizer.apply_push from the
    scatter output [D(+Dex)+4, >=n] (slot column already
    first-occurrence-exact).  d_main: the mf width when the payload also
    carries expand-embedding columns (they split into g_embedx_ex)."""
    d = delta.shape[0] - 4
    if d_main is None:
        d_main = d
    acc = {
        "g_show": delta[0, :n],
        "g_click": delta[1, :n],
        "g_embed": delta[2, :n],
        "g_embedx": delta[3:3 + d_main, :n].T,
        "slot": jnp.rint(delta[d + 3, :n]).astype(jnp.int32),
    }
    if d_main < d:
        acc["g_embedx_ex"] = delta[3 + d_main:3 + d, :n].T
    return acc


def _pull_sorted(ws: Dict[str, jnp.ndarray], plan, dims: sp.SpmmDims,
                 interpret: bool) -> jnp.ndarray:
    """The sorted-domain half of a pull, [3 + D, p_pad]: show, click,
    embed_w and the mf columns times the row's created mask, one column a
    kept sorted position."""
    from paddlebox_tpu import flags
    d = ws["mf"].shape[1] + _ex_dim(ws)
    rows2d, ch, tl, fg = plan[0], plan[3], plan[4], plan[5]
    tab = _pull_table(ws, dims)
    with trace.device_scope("ps.pull.gather"):
        g = sp.gather_sorted(tab, rows2d, ch, tl, fg,
                             plan_eff_dims(plan, dims) or dims,
                             interpret=interpret)          # [3+D+1, p_pad]
        # created-mask the mf rows in the SORTED domain: the mf_size column
        # is consumed here and never rides the crossing (w shrinks by 1,
        # and the canonical-domain mask multiply disappears)
        created = (g[3 + d:4 + d] > 0).astype(g.dtype)     # [1, p_pad]
        g = jnp.concatenate([g[:3], g[3:3 + d] * created], axis=0)
        if flags.get_flags("mxu_crossing_bf16"):
            g = g.astype(jnp.bfloat16)
    return g


# -- the layout the "take" pull crossing gathers in -------------------------
# XLA's TPU gather writes a row of up to 56 columns feature-major ({0,1}:
# one access a sublane tile of 8 columns) and is quick at that only while
# its source [round_up(W, 8), p_pad] stays in the chip's fast memory; out
# of it a row costs ~11 ns a tile instead of ~2.7.  A row-major source
# whose rows are padded to the lane width is gathered whole tiles at a
# time, ~20 ns a row whatever the width, relayout included.  Readings on a
# v5e (PR 31, PERF.md §6: take + pooling sum, 950,272 rows out in Wide&Deep's
# two capacity groups; ms feature-major / row-major):
#   W   sorted source     feature-major source   ms fm / rm
#   11  425,984 rows      26 MiB                 2.08 / 9.77   (DeepFM: 425,984 rows out)
#   11  851,968           52 MiB                 4.67 / 19.11
#   19  851,968           78 MiB                 8.09 / 19.17
#   27  851,968          104 MiB                10.75 / 19.25
#   35  638,976           97.5 MiB              12.86 / 18.97
#   35  786,432          120 MiB                50.88 / 19.14
#   35  851,968          130 MiB                53.30 / 19.30  (Wide&Deep)
#   43  638,976          117 MiB                61.27 / 19.02
#   19  1,277,952        117 MiB                34.93 / 20.24
#   11  1,703,936        104 MiB                 6.47 / 21.21
#   11  2,097,152        128 MiB                28.51 / 22.21
#   56  851,968          182 MiB                65.22 / 19.45
#   59  851,968          (row-major by itself)  17.41 / 19.72
#   67  851,968          (row-major by itself)  17.53 / 19.83
#  131  851,968          (row-major by itself)  23.31 / 27.20  (256 lanes)
CROSS_LANES = 128                       # lanes of a tile: the padded row
CROSS_FAST_SOURCE_BYTES = 110 << 20     # 104 MiB still gathers fast, 117 not
CROSS_LANES_MIN_WIDTH = 17  # two tiles or fewer: 1.14-1.28x, an 8x+ source
CROSS_LANES_MAX_WIDTH = 56  # wider, the compiler lays rows row-major itself


def cross_lane_width(w: int, p_pad: int, itemsize: int = 4,
                     crossing: str = "take") -> int:
    """Padded row width the pull crossing gathers at, from the static
    shape of the sorted columns [w, p_pad] alone (the gauge
    ``ps.mxu.pull_cross_lane_width``): CROSS_LANES where the feature-major
    gather would read a source too large for fast memory, 0 = the
    feature-major gather (and under "sort", which gathers nothing)."""
    if crossing != "take" or not (
            CROSS_LANES_MIN_WIDTH <= w <= CROSS_LANES_MAX_WIDTH):
        return 0
    sublanes = 32 // itemsize           # rows of a tile: 8 float32, 16 bf16
    fm_bytes = -(-w // sublanes) * sublanes * p_pad * itemsize
    return CROSS_LANES if fm_bytes > CROSS_FAST_SOURCE_BYTES else 0


def pull_cross_lane_width(ws: Dict[str, jnp.ndarray], plan,
                          dims: sp.SpmmDims, crossing: str = "take") -> int:
    """``cross_lane_width`` of the step that pulls ``ws`` by ``plan``."""
    from paddlebox_tpu import flags
    return cross_lane_width(
        3 + ws["mf"].shape[1] + _ex_dim(ws),
        (plan_eff_dims(plan, dims) or dims).p_pad,
        2 if flags.get_flags("mxu_crossing_bf16") else 4, crossing)


def _lane_rows(g: jnp.ndarray) -> Optional[jnp.ndarray]:
    """Sorted columns ``g`` [W, p_pad] as the row-major, lane-wide source
    of the crossing, [p_pad, CROSS_LANES] (the W columns, zero lanes
    beyond); None where ``cross_lane_width`` leaves the row feature-major.
    One relayout a step: the barrier keeps the compiler from folding it
    back into every gather that reads it."""
    w = g.shape[0]
    lanes = cross_lane_width(w, g.shape[1], g.dtype.itemsize)
    if not lanes:
        return None
    return jax.lax.optimization_barrier(
        jnp.pad(g.T, ((0, 0), (0, lanes - w))))


def _take_canonical(g: jnp.ndarray, inv_perm: jnp.ndarray,
                    dims: sp.SpmmDims, trimmed: bool,
                    src: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The "take" pull crossing: sorted columns ``g`` [W, p_pad] → one row
    [W] a canonical position of ``inv_perm`` (all of the plan's, or any
    subset of them).  ``src``: ``_lane_rows(g)``, made once by a caller
    that takes more than once from the same columns."""
    if src is None:
        if not trimmed:
            return jnp.take(g.T[:dims.p], inv_perm, axis=0)  # canonical [p,W]
        # trimmed plan: dropped positions (inv_perm < 0) were row-0
        # occurrences whose pull value is exactly zero — clamp + mask
        v = jnp.take(g.T, jnp.maximum(inv_perm, 0), axis=0)
        return v * (inv_perm >= 0).astype(v.dtype)[:, None]
    # row-major at lane width: the same rows (inv_perm < p, so no slice
    # to p), and the W lanes that hold them handed back.  The compiler
    # keeps the gather's [rows, CROSS_LANES] output row-major by itself,
    # also with the pooling sum behind it (PR 31: no second barrier)
    v = jnp.take(src, jnp.maximum(inv_perm, 0) if trimmed else inv_perm,
                 axis=0)[:, :g.shape[0]]
    if trimmed:
        v = v * (inv_perm >= 0).astype(v.dtype)[:, None]
    return v


def pull_rows(ws: Dict[str, jnp.ndarray], plan, dims: sp.SpmmDims,
              shape_slb: Tuple[int, int, int], interpret: bool = False,
              crossing: str = "take") -> jnp.ndarray:
    """Per-occurrence pull values in canonical order, [S, L, B, 3 + D]:
    show, click, embed_w and the mf columns times the row's created mask.

    Row 0 and the sentinel tile hold zeros, so padding occurrences and
    unseen keys read zeros — no length mask needed on the pull side.
    crossing: sorted→canonical lowering (ops/crossing.py) — "take" gathers
    by inv_perm, "sort" re-sorts keyed by perm (the destination index).
    """
    from paddlebox_tpu.ops import crossing as cx
    assert crossing in ("take", "sort"), crossing
    s, l, b = shape_slb
    perm, inv_perm = plan[1], plan[2]
    eff = plan_eff_dims(plan, dims)
    g = _pull_sorted(ws, plan, dims, interpret)
    w = g.shape[0]
    with trace.device_scope("ps.pull.cross"):
        if crossing == "sort":
            if eff is not None:
                # dropped (row-0) positions re-enter as leading zero
                # columns — exactly the value row 0 holds
                p0 = dims.p_pad - eff.p_pad
                g = jnp.concatenate([jnp.zeros((w, p0), g.dtype), g],
                                    axis=1)
            v = cx.permute_by_dest(tuple(g[:, :dims.p]), perm).T  # [p, W]
        else:
            v = _take_canonical(g, inv_perm, dims, eff is not None,
                                _lane_rows(g))
        return v.reshape(s, l, b, w).astype(jnp.float32)


def capacity_groups(capacities: Optional[Sequence[int]], s: int,
                    l: int) -> Tuple[Tuple[int, Tuple[int, ...]], ...]:
    """Slots of equal declared capacity, ``((c, slots), ...)`` by rising
    ``c``; None = every slot holds ``l``.  The canonical rectangle
    [S, L, B] is storage at the widest slot's capacity; a slot's positions
    at or beyond its own hold padding in every batch (the packers clip
    there: BatchPacker.pad_sparse)."""
    if capacities is None:
        return ((l, tuple(range(s))),)
    if len(capacities) != s or not all(1 <= c <= l for c in capacities):
        raise ValueError(
            f"capacities {tuple(capacities)} do not fit {s} slots of at "
            f"most {l} positions")
    return tuple((c, tuple(i for i in range(s) if capacities[i] == c))
                 for c in sorted(set(capacities)))


def pull_cross_rows(capacities: Optional[Sequence[int]],
                    shape_slb: Tuple[int, int, int],
                    crossing: str = "take") -> int:
    """Rows the pooled pull crossing emits a step (the gauge
    ``ps.mxu.pull_cross_rows``): the positions the slots can hold under
    "take", the whole rectangle under "sort"."""
    s, l, b = shape_slb
    if crossing != "take":
        return s * l * b
    return b * sum(c * len(slots)
                   for c, slots in capacity_groups(capacities, s, l))


def _runs(slots: Sequence[int]):
    """Consecutive runs of an ascending slot list, as (first position in
    the list, first slot, length)."""
    for _, run in itertools.groupby(enumerate(slots), lambda t: t[1] - t[0]):
        run = list(run)
        yield run[0] + (len(run),)


def pull_pool_cvm(ws: Dict[str, jnp.ndarray], plan, dims: sp.SpmmDims,
                  shape_slb: Tuple[int, int, int], use_cvm: bool = True,
                  interpret: bool = False, crossing: str = "take",
                  capacities: Optional[Sequence[int]] = None
                  ) -> jnp.ndarray:
    """Fused pull + seqpool + CVM → pooled [B, S, 3 + D]: ``pull_rows``
    summed over each slot's capacity.

    capacities: the slots' declared capacities (static).  The "take"
    crossing then runs once a capacity group and emits only the positions
    a slot can hold, ``sum(capacities) * B`` rows and not ``S * L * B``:
    the ones left out held exact zeros, so the sums are the same.  One
    group (every slot declares the same, or None) is the full-rectangle
    path, op for op."""
    s, l, b = shape_slb
    groups = capacity_groups(capacities, s, l)
    if len(groups) == 1 or crossing != "take":
        v = pull_rows(ws, plan, dims, shape_slb, interpret, crossing)
        return pool_cvm_values(v, use_cvm, premasked=True)
    g = _pull_sorted(ws, plan, dims, interpret)
    with trace.device_scope("ps.pull.cross"):
        src = _lane_rows(g)     # once, for every capacity group
    trimmed = plan_eff_dims(plan, dims) is not None
    ip = plan[2].reshape(s, l, b)
    pieces = []                 # (first slot, pooled [B, run, 3 + D])
    for c, slots in groups:
        runs = list(_runs(slots))
        with trace.device_scope("ps.pull.cross"):
            ip_c = jnp.concatenate([ip[s0:s0 + n, :c] for _, s0, n in runs])
            v = _take_canonical(g, ip_c.reshape(-1), dims, trimmed, src)
            v = v.reshape(len(slots), c, b, -1).astype(jnp.float32)
        pooled = pool_cvm_values(v, use_cvm, premasked=True)
        with trace.device_scope("ps.pull.pool"):
            pieces += [(s0, pooled[:, j:j + n]) for j, s0, n in runs]
    # back into slot order: the runs' static slices, by first slot
    with trace.device_scope("ps.pull.pool"):
        return jnp.concatenate(
            [x for _, x in sorted(pieces, key=lambda t: t[0])], axis=1)


def occurrence_payload(d_occ: jnp.ndarray, ins_cvm: jnp.ndarray,
                       slot_ids: jnp.ndarray) -> jnp.ndarray:
    """Canonical push payload [S, L, B, D+4] from a gradient each
    occurrence owns: ``d_occ`` [S, L, B, 1+D] is (g_embed, g_mf x D) of the
    row pulled at that position (a sequence model's rows are not pooled, so
    nothing is broadcast over L); g_show, g_click and slot are the
    instance's, as in ``push_payload``."""
    s, l, b = d_occ.shape[:3]
    g_show = jnp.broadcast_to(ins_cvm[None, None, :, 0], (s, l, b))
    g_click = jnp.broadcast_to(ins_cvm[None, None, :, 1], (s, l, b))
    slot_col = jnp.broadcast_to(
        slot_ids.astype(jnp.float32)[:, None, None], (s, l, b))
    return jnp.concatenate(
        [jnp.stack([g_show, g_click], axis=-1), d_occ, slot_col[..., None]],
        axis=-1)


def pull_head(ws: Dict[str, jnp.ndarray], head_rows: jnp.ndarray
              ) -> jnp.ndarray:
    """The rows of a tied head, [V, D]: what a pull of those keys returns
    (``pull_rows``'s mf columns: the row times its created mask, so a row
    the table has not created yet reads zero), once a step and not per
    position.  ``head_rows`` [V]: their working-set rows (the feed's
    ``head_rows`` plane)."""
    created = jnp.take(ws["mf_size"], head_rows) > 0
    return jnp.take(ws["mf"], head_rows, axis=0) \
        * created[:, None].astype(ws["mf"].dtype)


def merge_head_grad(acc: Dict[str, jnp.ndarray], head_rows: jnp.ndarray,
                    d_head: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """The merged per-row accumulators with a tied head's gradient
    ``d_head`` [V, D] added to ``g_embedx`` at ``head_rows`` [V].  Nothing
    else moves: g_show / g_click count occurrences only, so the rule
    (``optimizer.push_touched``) still updates only the rows an
    occurrence touched, and divides their merged gradient by their
    shows.  The rows are distinct, so the add is a gather: a working-set
    row reads the head's gradient at its own place in the head, or
    nothing."""
    n, v = acc["g_embedx"].shape[0], head_rows.shape[0]
    place = jnp.full((n,), -1, jnp.int32).at[head_rows].set(
        jnp.arange(v, dtype=jnp.int32))
    add = jnp.take(d_head, jnp.maximum(place, 0), axis=0) \
        * (place >= 0).astype(d_head.dtype)[:, None]
    return {**acc, "g_embedx": acc["g_embedx"] + add}


def _push_sorted(ws: Dict[str, jnp.ndarray], plan, dims: sp.SpmmDims,
                 idx_slb: jnp.ndarray, d_pooled: jnp.ndarray,
                 ins_cvm: jnp.ndarray, slot_ids: jnp.ndarray,
                 crossing: str, d_occ: jnp.ndarray) -> jnp.ndarray:
    """The canonical→sorted half of a push: the payload in the sorted
    domain, [padded_width(D + 4), p_pad kept], one column a kept sorted
    position (``push_and_update`` has the contract).
    """
    from paddlebox_tpu import flags
    from paddlebox_tpu.ops import crossing as cx
    assert crossing in ("take", "sort"), crossing
    s, l, b = idx_slb.shape
    d = ws["mf"].shape[1] + _ex_dim(ws)
    w = d + 4
    perm, inv_perm, first_occ = plan[1], plan[2], plan[7]
    eff = plan_eff_dims(plan, dims)
    kd = eff or dims
    bf16 = bool(flags.get_flags("mxu_crossing_bf16"))

    if d_occ is not None and crossing != "take":
        raise ValueError("a per-occurrence push crosses by take only")

    def kept_perm():
        # the kept suffix of the full bijection — dropped row-0
        # occurrences never scatter (row 0 is reserved, optimizer.py:17)
        # and sentinel tail positions read canonical 0 but land in the
        # discarded sentinel tile
        return jnp.concatenate(
            [perm, jnp.zeros((dims.p_pad - dims.p,), jnp.int32)]
        )[dims.p_pad - kd.p_pad:]

    if len(plan) > 8:
        bs_ids, labelcol, slotcol = plan[8], plan[9], plan[10]
        if d_occ is not None:
            dyn = jnp.take(d_occ.reshape(dims.p, 1 + d), kept_perm(),
                           axis=0).T                           # [1+D, p_pad]
        else:
            # dynamic columns only: [B*S, 1+D] (b-major, bs = b*S + s)
            p2 = d_pooled[:, :, 2:].reshape(b * s, 1 + d)
            if bf16:
                p2 = p2.astype(jnp.bfloat16)
            if crossing == "sort":
                # canonical flat [(s,l,b), 1+D] — broadcast over L only
                # here, in the narrow dynamic slice
                can = jnp.broadcast_to(
                    jnp.transpose(p2.reshape(b, s, 1 + d),
                                  (1, 0, 2))[:, None],
                    (s, l, b, 1 + d)).reshape(dims.p, 1 + d)
                dyn = cx.permute_by_dest(tuple(can.T), inv_perm)  # [1+D, p]
                if eff is not None:
                    dyn = dyn[:, dims.p_pad - eff.p_pad:]
                pad = kd.p_pad - dyn.shape[1]
                dyn = jnp.concatenate(
                    [dyn, jnp.zeros((1 + d, pad), dyn.dtype)], axis=1)
            else:
                dyn = jnp.take(p2, bs_ids, axis=0).T           # [1+D, p_pad]
        dyn = dyn.astype(jnp.float32)
        ones = jnp.ones((1, kd.p_pad), jnp.float32)
        srt_cm = jnp.concatenate(
            [ones, labelcol[None], dyn, slotcol[None]], axis=0)
    else:
        # NOTE: mxu_crossing_bf16 is intentionally NOT applied here — the
        # legacy payload carries the slot-id column, which must stay exact
        # (ids beyond 8 mantissa bits would round in bf16 and silently
        # break the optimizer's exact slot matches: nodeid_slot,
        # slot_mf_dims), so the bandwidth lever only pays on the planes
        # path where slot rides a separate static f32 plane.
        payload = (push_payload(d_pooled, ins_cvm, slot_ids, (s, l, b))
                   if d_occ is None
                   else occurrence_payload(d_occ, ins_cvm, slot_ids))
        flat = payload.reshape(dims.p, w)
        if crossing == "sort":
            # destination = this element's sorted position (shifted
            # kept-domain position when trimmed: negatives sort first =
            # dropped prefix)
            srt_cm = cx.permute_by_dest(tuple(flat.T), inv_perm)   # [w, p]
            if eff is not None:
                srt_cm = srt_cm[:, dims.p_pad - eff.p_pad:]
            pad = kd.p_pad - srt_cm.shape[1]
            srt_cm = jnp.concatenate(
                [srt_cm, jnp.zeros((w, pad), srt_cm.dtype)], axis=1)
        elif eff is None:
            srt = jnp.take(flat, perm, axis=0)             # sorted domain
            srt_cm = jnp.concatenate(
                [srt, jnp.zeros((dims.p_pad - dims.p, w), srt.dtype)]).T
        else:
            srt_cm = jnp.take(flat, kept_perm(), axis=0).T
        srt_cm = srt_cm.astype(jnp.float32)
        # slot column: keep only each row's FIRST occurrence (plan mask), so
        # the scatter-sum returns that occurrence's slot exactly — no
        # averaging, and keys appearing under several slots resolve
        # deterministically (≙ the reference's per-key slot from its merge
        # position, box_wrapper.cu:417 PushMergeCopy)
        srt_cm = srt_cm.at[w - 1, :].mul(first_occ)
    wp = sp.padded_width(w)
    if wp != w:                     # whole kernel blocks of rows
        srt_cm = jnp.concatenate(
            [srt_cm, jnp.zeros((wp - w, kd.p_pad), jnp.float32)], axis=0)
    return srt_cm


def push_and_update(ws: Dict[str, jnp.ndarray], plan, dims: sp.SpmmDims,
                    idx_slb: jnp.ndarray, d_pooled: jnp.ndarray,
                    ins_cvm: jnp.ndarray, slot_ids: jnp.ndarray,
                    cfg: SparseSGDConfig,
                    interpret: bool = False,
                    crossing: str = "take",
                    d_occ: jnp.ndarray = None,
                    head=None) -> Dict[str, jnp.ndarray]:
    """Merged push + sparse optimizer.

    d_occ [S, L, B, 1+D], in place of d_pooled (then None): a gradient per
    occurrence (``occurrence_payload``), for rows that were pulled
    unpooled; it crosses by ``perm`` ("take" only), the rest is the same.

    head: ``(head_rows [V], d_head [V, D])`` of a model whose head is the
    table's rows — the gradient a row, merged with the occurrences' after
    the scatter and before the rule (``merge_head_grad``).

    d_pooled [B, S, 3+D] — cols 0,1 are ignored and replaced by the
    instance cvm (reference push semantics, box_wrapper_impl.h:373);
    ins_cvm [B, 2]; slot_ids [S].
    crossing: canonical→sorted lowering (ops/crossing.py) — "take" gathers
    by perm, "sort" re-sorts keyed by inv_perm (the destination index).

    When the plan carries static sorted-domain planes (len > 8: bs,
    labelcol, slotcol — pass_feed builds them at feed time), only the
    DYNAMIC payload columns cross (g_embed + D×g_mf = 1+D channels):
    g_show ≡ 1 rides as a constant, g_click and slot are feed-time planes
    (the label and slot of an occurrence never change within a pass), and
    the crossing gathers from the [B*S, 1+D] pooled-grad matrix instead of
    a materialized [S, L, B, D+4] broadcast — the payload is constant over
    L, so the broadcast carried 3x redundant rows through the crossing.
    ≙ CopyForPush building the payload directly per key slot,
    box_wrapper.cu:1168.
    """
    w = ws["mf"].shape[1] + _ex_dim(ws) + 4
    rows2d, ch, tl, fs = plan[0], plan[3], plan[4], plan[6]
    with trace.device_scope("ps.push.cross"):
        srt_cm = _push_sorted(ws, plan, dims, idx_slb, d_pooled, ins_cvm,
                              slot_ids, crossing, d_occ)
    with trace.device_scope("ps.push.scatter"):
        delta = sp.scatter_add_sorted(
            srt_cm, rows2d, ch, tl, fs, plan_eff_dims(plan, dims) or dims,
            interpret=interpret)                           # [D+4, n_kernel]
        if delta.shape[0] != w:         # whole kernel blocks of rows
            delta = delta[:w]
    with trace.device_scope("ps.push.rule"):
        acc = acc_from_delta(delta, ws["show"].shape[0],
                             d_main=ws["mf"].shape[1])
        if head is not None:
            with trace.device_scope("seq.head_push"):
                acc = merge_head_grad(acc, *head)
        return sparse_opt.apply_push(ws, acc, cfg)
