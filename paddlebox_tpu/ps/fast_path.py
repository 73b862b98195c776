"""TPU-tiling-aware fast path for the sparse pull/pool/push pipeline.

Why this exists: TPU tiles the last two dims of every array to (8, 128)
(f32).  The straightforward layout — embeddings [S, B, L, E] with L≈1, E≈11
— pads 1→8 sublanes and 11→128 lanes, a ~90x HBM-traffic blowup on every
elementwise op, and the whole-table optimizer pays 16x on [N, D] state.
Measured on v5e this made the fused step ~20x slower than the math requires.

Fast-path rules implemented here:
* index tensors are [S, L, B] — batch minor, so every scalar intermediate
  ([S, L, B], [S, B]) tiles perfectly;
* per-feature scalars stay [N] 1-D (no padding);
* the only E-minor tensors are the unavoidable mf gathers, touched O(1)
  times each;
* NO full-table [N, D] elementwise pass in the optimizer: merged grads are
  scattered once, gathered back per occurrence, updated row-wise in the
  batch domain, and scatter-.set back (duplicate occurrences write
  identical values, so the .set is deterministic).

Semantics are bit-for-bit the v1 path (embedding.py + optimizer.py — itself
matching optimizer.cuh.h:31-130); tests/test_fast_path.py asserts equality.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import SparseSGDConfig


def step_prelude(idx: jnp.ndarray, lengths: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                            jnp.ndarray]:
    """Shared per-step mask/flatten prelude: (m, safe_idx, flat, occ).

    pull_pool_cvm and push_and_update both need the length mask (and push
    its flattened forms); computing it once per step and passing it to
    both halves saves a [S, L, B] broadcast-compare + where + reshape per
    step.  Pure function of the batch planes — training-state-free.
    """
    S, L, B = idx.shape
    m = (jnp.arange(L)[None, :, None] < lengths[:, None, :]).astype(
        jnp.float32)                                       # [S, L, B]
    safe_idx = jnp.where(m > 0, idx, 0)
    return m, safe_idx, safe_idx.reshape(-1), m.reshape(-1)


def pull_pool_cvm(ws: Dict[str, jnp.ndarray], idx: jnp.ndarray,
                  lengths: jnp.ndarray, use_cvm: bool = True,
                  prelude: Optional[Tuple] = None) -> jnp.ndarray:
    """Fused pull + seqpool + CVM.

    idx: [S, L, B] pass rows (0 = padding); lengths: [S, B].
    → pooled [B, S, E] with E = 3 + D (cols: cvm'show, cvm'click, w, mf...).
    prelude: optional step_prelude(idx, lengths) result shared with
    push_and_update; computed here when absent (back-compat callers).
    """
    S, L, B = idx.shape
    m = (prelude[0] if prelude is not None
         else step_prelude(idx, lengths)[0]).astype(
        ws["show"].dtype)                                  # [S, L, B]
    show = jnp.sum(ws["show"][idx] * m, axis=1)            # [S, B]
    click = jnp.sum(ws["click"][idx] * m, axis=1)
    w = jnp.sum(ws["embed_w"][idx] * m, axis=1)
    created = (ws["mf_size"][idx] > 0).astype(m.dtype) * m
    from paddlebox_tpu.ps.embedding import mf_values
    mf_rows = mf_values(ws, ws["mf"][idx])  # dequant if serving-frozen
    mf = jnp.einsum("slbd,slb->sbd", mf_rows, created)     # [S, B, D]
    if use_cvm:
        show_t = jnp.log(show + 1.0)
        click_t = jnp.log(click + 1.0) - show_t
    else:
        show_t, click_t = show, click
    head = jnp.stack([show_t, click_t, w], axis=-1)        # [S, B, 3]
    pooled = jnp.concatenate([head, mf], axis=-1)          # [S, B, E]
    return jnp.transpose(pooled, (1, 0, 2))                # [B, S, E]


def push_and_update(ws: Dict[str, jnp.ndarray], idx: jnp.ndarray,
                    lengths: jnp.ndarray, d_pooled: jnp.ndarray,
                    ins_cvm: jnp.ndarray, slot_ids: jnp.ndarray,
                    cfg: SparseSGDConfig,
                    prelude: Optional[Tuple] = None) -> Dict[str, jnp.ndarray]:
    """Merged push + sparse adagrad, batch-domain for the mf table.

    idx [S, L, B]; d_pooled [B, S, E] (model grads wrt pull_pool_cvm output
    — cols 0,1 ignored, replaced by ins_cvm per the reference push
    semantics); ins_cvm [B, 2]; slot_ids [S]; prelude: optional shared
    step_prelude(idx, lengths) result (padding occurrences scatter into
    reserved row 0 via safe_idx).
    """
    S, L, B = idx.shape
    n = ws["show"].shape[0]
    D = ws["mf"].shape[1]
    m, safe_idx, flat, occ = (prelude if prelude is not None
                              else step_prelude(idx, lengths))

    # -- merged per-row accumulators ([N] scalars; [N, D] once for mf) ----
    g_show = jnp.zeros((n,), jnp.float32).at[flat].add(
        occ * jnp.broadcast_to(ins_cvm[None, None, :, 0], (S, L, B)
                               ).reshape(-1))
    g_click = jnp.zeros((n,), jnp.float32).at[flat].add(
        occ * jnp.broadcast_to(ins_cvm[None, None, :, 1], (S, L, B)
                               ).reshape(-1))
    d_w = jnp.transpose(d_pooled[:, :, 2], (1, 0))         # [S, B]
    g_embed = jnp.zeros((n,), jnp.float32).at[flat].add(
        occ * jnp.broadcast_to(d_w[:, None, :], (S, L, B)).reshape(-1))
    d_mf = jnp.transpose(d_pooled[:, :, 3:], (1, 0, 2))    # [S, B, D]
    d_mf_occ = jnp.broadcast_to(d_mf[:, None], (S, L, B, D)) \
        * m[..., None]
    g_mf = jnp.zeros((n, D), jnp.float32).at[flat].add(
        d_mf_occ.reshape(-1, D))
    slot_occ = jnp.broadcast_to(
        slot_ids[:, None, None].astype(jnp.int32), (S, L, B)).reshape(-1)
    slot_acc = jnp.zeros((n,), jnp.int32).at[flat].max(
        jnp.where(occ > 0, slot_occ, 0))

    # -- scalar state: full-table [N] ops (8MB/pass — cheap) --------------
    # PB301 suppressions below: these 1-D [N] scalar sweeps are this
    # path's documented contract (module docstring — "per-feature scalars
    # stay [N] 1-D").
    from paddlebox_tpu.ps.optimizer import push_touched
    touched = push_touched(ws, {"g_show": g_show})
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    show = jnp.where(touched, ws["show"] + g_show, ws["show"])
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    click = jnp.where(touched, ws["click"] + g_click, ws["click"])
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    delta = jnp.where(
        touched,
        ws["delta_score"] + cfg.nonclk_coeff * (g_show - g_click)
        + cfg.clk_coeff * g_click,
        ws["delta_score"])
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    slot = jnp.where(touched, slot_acc, ws["slot"])
    lr_embed = jnp.where(slot == cfg.nodeid_slot, cfg.learning_rate,
                         cfg.feature_learning_rate)
    safe_scale = jnp.where(g_show > 0, g_show, 1.0)
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    ratio = lr_embed * jnp.sqrt(cfg.initial_g2sum /
                                (cfg.initial_g2sum + ws["embed_g2sum"]))
    sg = g_embed / safe_scale
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    embed_w = jnp.where(
        touched,
        jnp.clip(ws["embed_w"] + sg * ratio, cfg.min_bound, cfg.max_bound),
        ws["embed_w"])
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    embed_g2sum = jnp.where(touched, ws["embed_g2sum"] + sg * sg,
                            ws["embed_g2sum"])
    score = cfg.nonclk_coeff * (show - click) + cfg.clk_coeff * click
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    create = touched & (ws["mf_size"] == 0) & \
        (score >= cfg.mf_create_thresholds)
    # dynamic per-slot dims (≙ CtrDymfAccessor): created rows record their
    # slot's true width, resolved from the MERGED row slot (same chain the
    # optimizer rules use — keeps multi-slot keys deterministic)
    from paddlebox_tpu.ps.optimizer import _dym_dims
    dims_row = _dym_dims(cfg, slot, D)
    # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
    mf_size = jnp.where(create,
                        dims_row if dims_row is not None else D,
                        ws["mf_size"])

    # -- mf: batch-domain row updates (no [N, D] full pass) ---------------
    # gather merged values back per occurrence; every occurrence of a row
    # computes the identical new row, so scatter-.set is deterministic.
    r_gshow = g_show[flat]                                 # [P]
    r_g2 = ws["mf_g2sum"][flat]
    r_trainable = (ws["mf_size"][flat] > 0) & (r_gshow > 0) & (flat != 0)
    r_scale = jnp.where(r_gshow > 0, r_gshow, 1.0)
    r_ratio = cfg.mf_learning_rate * jnp.sqrt(
        cfg.mf_initial_g2sum / (cfg.mf_initial_g2sum + r_g2))
    r_g = g_mf[flat] / r_scale[:, None]                    # [P, D]
    r_mf = ws["mf"][flat]
    new_mf = jnp.clip(r_mf + r_g * r_ratio[:, None],
                      cfg.mf_min_bound, cfg.mf_max_bound)
    # mean-square divisor is the ROW's true dim (merged slot, gathered per
    # occurrence like the other row state — every occurrence of a row then
    # computes the identical update, preserving the .set determinism)
    if dims_row is not None:
        new_g2 = r_g2 + jnp.sum(r_g * r_g, axis=1) \
            / dims_row[flat].astype(jnp.float32)
    else:
        new_g2 = r_g2 + jnp.sum(r_g * r_g, axis=1) / D
    write_idx = jnp.where(r_trainable, flat, 0)
    mf = ws["mf"].at[write_idx].set(
        jnp.where(r_trainable[:, None], new_mf, ws["mf"][0][None, :]))
    mf = mf.at[0].set(0.0)  # keep the reserved row zero
    mf_g2sum = ws["mf_g2sum"].at[write_idx].set(
        jnp.where(r_trainable, new_g2, ws["mf_g2sum"][0]))
    mf_g2sum = mf_g2sum.at[0].set(ws["mf_g2sum"][0])

    out = {"show": show, "click": click, "delta_score": delta, "slot": slot,
           "embed_w": embed_w, "embed_g2sum": embed_g2sum,
           "mf_size": mf_size, "mf_g2sum": mf_g2sum, "mf": mf}
    if "show_acc" in ws:   # ctr_double: exact pass-delta counters
        # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
        out["show_acc"] = jnp.where(touched, ws["show_acc"] + g_show,
                                    ws["show_acc"])
        # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
        out["click_acc"] = jnp.where(touched, ws["click_acc"] + g_click,
                                     ws["click_acc"])
    for extra in ("mf_ex", "mf_ex_g2sum"):
        if extra in ws:
            out[extra] = ws[extra]
    return out
