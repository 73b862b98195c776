"""Device-side sparse optimizers over the pass working set.

≙ heter_ps/optimizer.cuh.h — SparseAdagradOptimizer (:31), SparseAdamOptimizer
(:148), SparseAdamSharedOptimizer (:330) — re-expressed as whole-table
vectorized updates: push accumulators hold the merged per-row gradients
(zero for untouched rows), the update is masked by ``touched = g_show > 0``
so untouched rows are bit-identical no-ops.  All [N]- or [N,D]-shaped
elementwise math → trivially fused by XLA behind the scatter-adds.

Exact semantics reproduced from dy_mf_update_value (optimizer.cuh.h:82-130):
  show  += g_show ; click += g_click
  delta_score += nonclk_coeff*(g_show-g_click) + clk_coeff*g_click
  embed_w: adagrad with lr scaled by sqrt(g0/(g0+g2sum)), grad scaled by
           1/g_show, clip to [min_bound, max_bound], g2sum += mean sq grad
  mf: created lazily when nonclk_coeff*(show-click)+clk_coeff*click crosses
      mf_create_thresholds (:104-112); then same adagrad with mf_* params.
Row 0 (reserved zero/padding row) is never updated.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import SparseSGDConfig


def _adagrad_update(w, g2sum, g, scale, lr, initial_g2sum, min_bound,
                    max_bound, touched, n_dim):
    """≙ update_value_work (optimizer.cuh.h:43-73), vectorized over rows.

    w: [N] or [N,D]; g2sum: [N]; g: same shape as w; scale: [N] (g_show).
    n_dim: the embedx group width — a scalar, or per-row [N] ints for
    dynamic mf dims (≙ CtrDymfAccessor: the mean-square divisor is the
    row's TRUE dim; tail-column grads arrive as exact zeros).
    """
    safe_scale = jnp.where(scale > 0, scale, 1.0)
    ratio = lr * jnp.sqrt(initial_g2sum / (initial_g2sum + g2sum))
    if w.ndim == 2:
        scaled_grad = g / safe_scale[:, None]
        new_w = w + scaled_grad * ratio[:, None]
        add_g2sum = jnp.sum(scaled_grad * scaled_grad, axis=1) / n_dim
    else:
        scaled_grad = g / safe_scale
        new_w = w + scaled_grad * ratio
        add_g2sum = scaled_grad * scaled_grad
    new_w = jnp.clip(new_w, min_bound, max_bound)
    mask = touched if w.ndim == 1 else touched[:, None]
    return (jnp.where(mask, new_w, w),
            jnp.where(touched, g2sum + add_g2sum, g2sum))


def push_touched(ws, acc):
    """THE touched mask: rows this push updates (g_show > 0, reserved row
    0 excluded).  Single source for every rule, the fast path, and the
    ctr_double delta counters — they must agree bit-exactly."""
    row = jnp.arange(ws["show"].shape[0])
    return (acc["g_show"] > 0) & (row != 0)


def _common_stats(ws, acc, cfg):
    """Shared show/click/delta accumulation + touched mask (the common
    prologue of every rule, ≙ optimizer.cuh.h:84-101)."""
    touched = push_touched(ws, acc)
    show = jnp.where(touched, ws["show"] + acc["g_show"], ws["show"])
    click = jnp.where(touched, ws["click"] + acc["g_click"], ws["click"])
    delta = jnp.where(
        touched,
        ws["delta_score"] + cfg.nonclk_coeff * (acc["g_show"] - acc["g_click"])
        + cfg.clk_coeff * acc["g_click"],
        ws["delta_score"])
    return touched, show, click, delta


def _mf_create(ws, cfg, touched, show, click, mf_dim):
    """Lazy mf creation on the post-accumulation show/click
    (optimizer.cuh.h:104-112); rows created this push keep their candidate
    init (the reference returns right after initialization, :113-127).
    mf_dim may be per-row [N] for dynamic dims (created rows get THEIR
    slot's width, ≙ CtrDymfAccessor feature_value.h:42)."""
    score = cfg.nonclk_coeff * (show - click) + cfg.clk_coeff * click
    create = touched & (ws["mf_size"] == 0) & \
        (score >= cfg.mf_create_thresholds)
    mf_size = jnp.where(create, mf_dim, ws["mf_size"])
    mf_touched = touched & (ws["mf_size"] > 0)
    return create, mf_size, mf_touched



def _dym_dims(cfg, slot, mf_dim):
    """Per-row mf dims from the merged slot ids via a fused where-chain
    (NOT a gather — k compares over [N] cost ~nothing; ≙ CtrDymfAccessor
    resolving dim by slot, ctr_dymf_accessor.h).  None when the config has
    no dynamic dims."""
    if not getattr(cfg, "slot_mf_dims", ()):
        return None
    dims = jnp.full(slot.shape, mf_dim, jnp.int32)
    for sid, d in cfg.slot_mf_dims:
        dims = jnp.where(slot == sid, d, dims)
    return dims


def sparse_adagrad_apply(ws: Dict[str, jnp.ndarray],
                         acc: Dict[str, jnp.ndarray],
                         cfg: SparseSGDConfig,
                         dims_row=None) -> Dict[str, jnp.ndarray]:
    """One merged push → working-set update (≙ HashTable::update with
    SparseAdagradOptimizer, hashtable_kernel.cu + optimizer.cuh.h:31)."""
    touched, show, click, delta = _common_stats(ws, acc, cfg)
    slot = jnp.where(touched, acc["slot"], ws["slot"])

    # embed_w (1-dim lr weight); slot-dependent lr (optimizer.cuh.h:52-56)
    lr_embed = jnp.where(slot == cfg.nodeid_slot, cfg.learning_rate,
                         cfg.feature_learning_rate)
    safe_scale = jnp.where(acc["g_show"] > 0, acc["g_show"], 1.0)
    ratio = lr_embed * jnp.sqrt(cfg.initial_g2sum /
                                (cfg.initial_g2sum + ws["embed_g2sum"]))
    sg = acc["g_embed"] / safe_scale
    new_embed = jnp.clip(ws["embed_w"] + sg * ratio, cfg.min_bound,
                         cfg.max_bound)
    embed_w = jnp.where(touched, new_embed, ws["embed_w"])
    embed_g2sum = jnp.where(touched, ws["embed_g2sum"] + sg * sg,
                            ws["embed_g2sum"])

    # lazy mf creation on the *post-accumulation* show/click
    # (optimizer.cuh.h:104-112)
    mf_dim = ws["mf"].shape[1]
    if dims_row is None:
        dims_row = _dym_dims(cfg, slot, mf_dim)
    group_dim = dims_row if dims_row is not None else mf_dim
    create, mf_size, mf_touched = _mf_create(ws, cfg, touched, show, click,
                                             group_dim)
    mf, mf_g2sum = _adagrad_update(
        ws["mf"], ws["mf_g2sum"], acc["g_embedx"], acc["g_show"],
        cfg.mf_learning_rate, cfg.mf_initial_g2sum, cfg.mf_min_bound,
        cfg.mf_max_bound, mf_touched, group_dim)

    out = {"show": show, "click": click, "delta_score": delta, "slot": slot,
           "embed_w": embed_w, "embed_g2sum": embed_g2sum,
           "mf_size": mf_size, "mf_g2sum": mf_g2sum, "mf": mf}
    if "mf_ex" in ws:  # expand (NNCross) embedding trains like mf
        if "g_embedx_ex" in acc:
            mf_ex, mf_ex_g2 = _adagrad_update(
                ws["mf_ex"], ws["mf_ex_g2sum"], acc["g_embedx_ex"],
                acc["g_show"], cfg.mf_learning_rate, cfg.mf_initial_g2sum,
                cfg.mf_min_bound, cfg.mf_max_bound, mf_touched,
                ws["mf_ex"].shape[1])
            out["mf_ex"], out["mf_ex_g2sum"] = mf_ex, mf_ex_g2
        else:
            out["mf_ex"], out["mf_ex_g2sum"] = ws["mf_ex"], ws["mf_ex_g2sum"]
    return out


def _shared_adam_group(w, m1, m2, b1p, b2p, g, scale, lr, beta1, beta2,
                       min_bound, max_bound, touched, n_dim: int,
                       eps: float = 1e-8):
    """≙ SparseAdamSharedOptimizer::update_value_work
    (optimizer.cuh.h:341-386): ONE shared (moment1, moment2, beta-pow) per
    row for the whole group; per-dim new moments derive from the shared old
    moment, updated w per dim, then the stored moments are the per-dim
    means and the beta powers decay once."""
    safe_scale = jnp.where(scale > 0, scale, 1.0)
    ratio = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
    per_row_dim = getattr(n_dim, "ndim", 0) > 0
    if w.ndim == 2:
        sg = g / safe_scale[:, None]
        new_m1 = beta1 * m1[:, None] + (1 - beta1) * sg
        new_m2 = beta2 * m2[:, None] + (1 - beta2) * sg * sg
        upd = new_m1 / (jnp.sqrt(new_m2) + eps)
        if per_row_dim:
            # dynamic mf dims: only the row's true columns update, and the
            # shared moments are means over those columns alone
            dmask = (jnp.arange(w.shape[1])[None, :]
                     < n_dim[:, None]).astype(w.dtype)
            upd = upd * dmask
            m1_out = jnp.sum(new_m1 * dmask, axis=1) / n_dim
            m2_out = jnp.sum(new_m2 * dmask, axis=1) / n_dim
        else:
            m1_out = jnp.mean(new_m1, axis=1)
            m2_out = jnp.mean(new_m2, axis=1)
        new_w = w + ratio[:, None] * upd
        mask = touched[:, None]
    else:
        sg = g / safe_scale
        new_m1 = beta1 * m1 + (1 - beta1) * sg
        new_m2 = beta2 * m2 + (1 - beta2) * sg * sg
        new_w = w + ratio * (new_m1 / (jnp.sqrt(new_m2) + eps))
        m1_out, m2_out = new_m1, new_m2
        mask = touched
    new_w = jnp.clip(new_w, min_bound, max_bound)
    return (jnp.where(mask, new_w, w),
            jnp.where(touched, m1_out, m1),
            jnp.where(touched, m2_out, m2),
            jnp.where(touched, b1p * beta1, b1p),
            jnp.where(touched, b2p * beta2, b2p))


def sparse_adam_apply(ws: Dict[str, jnp.ndarray], acc: Dict[str, jnp.ndarray],
                      cfg: SparseSGDConfig,
                         dims_row=None) -> Dict[str, jnp.ndarray]:
    """Exact SparseAdamShared (optimizer.cuh.h:330-477): shared per-row
    moments in embed_gsum/embed_g2sum (+ beta powers) for the lr weight and
    mf_gsum/mf_g2sum for the embedx group.  Requires the adam state fields
    (feature_value.ADAM_FIELDS — created when config.sgd.optimizer is
    adam/shared_adam)."""
    touched, show, click, delta = _common_stats(ws, acc, cfg)
    slot = jnp.where(touched, acc["slot"], ws["slot"])

    embed_w, e_m1, e_m2, e_b1, e_b2 = _shared_adam_group(
        ws["embed_w"], ws["embed_gsum"], ws["embed_g2sum"],
        ws["embed_b1p"], ws["embed_b2p"], acc["g_embed"], acc["g_show"],
        cfg.learning_rate, cfg.beta1_decay_rate, cfg.beta2_decay_rate,
        cfg.mf_min_bound, cfg.mf_max_bound, touched, 1, cfg.ada_epsilon)

    mf_dim = ws["mf"].shape[1]
    if dims_row is None:
        dims_row = _dym_dims(cfg, slot, mf_dim)
    group_dim = dims_row if dims_row is not None else mf_dim
    create, mf_size, mf_touched = _mf_create(ws, cfg, touched, show, click,
                                             group_dim)
    mf, m_m1, m_m2, m_b1, m_b2 = _shared_adam_group(
        ws["mf"], ws["mf_gsum"], ws["mf_g2sum"], ws["mf_b1p"], ws["mf_b2p"],
        acc["g_embedx"], acc["g_show"], cfg.mf_learning_rate,
        cfg.beta1_decay_rate, cfg.beta2_decay_rate,
        cfg.mf_min_bound, cfg.mf_max_bound, mf_touched, group_dim,
        cfg.ada_epsilon)
    # rows created this push reset their beta powers to the decay rates
    # (creation init, optimizer.cuh.h:436-441)
    m_b1 = jnp.where(create, cfg.beta1_decay_rate, m_b1)
    m_b2 = jnp.where(create, cfg.beta2_decay_rate, m_b2)

    out = {"show": show, "click": click, "delta_score": delta,
           "slot": slot,
           "embed_w": embed_w, "embed_g2sum": e_m2, "embed_gsum": e_m1,
           "embed_b1p": e_b1, "embed_b2p": e_b2,
           "mf_size": mf_size, "mf_g2sum": m_m2, "mf_gsum": m_m1,
           "mf_b1p": m_b1, "mf_b2p": m_b2, "mf": mf}
    for extra in ("mf_ex", "mf_ex_g2sum"):
        if extra in ws:
            out[extra] = ws[extra]
    return out


def sparse_naive_apply(ws: Dict[str, jnp.ndarray],
                       acc: Dict[str, jnp.ndarray],
                       cfg: SparseSGDConfig,
                         dims_row=None) -> Dict[str, jnp.ndarray]:
    """SparseNaiveSGDRule (sparse_sgd_rule.h:77): plain SGD with bound
    clipping, show-scaled grads; g2sum fields unused."""
    touched, show, click, delta = _common_stats(ws, acc, cfg)
    slot = jnp.where(touched, acc["slot"], ws["slot"])
    safe_scale = jnp.where(acc["g_show"] > 0, acc["g_show"], 1.0)
    embed_w = jnp.where(
        touched,
        jnp.clip(ws["embed_w"] + cfg.learning_rate *
                 acc["g_embed"] / safe_scale, cfg.min_bound, cfg.max_bound),
        ws["embed_w"])
    mf_dim = ws["mf"].shape[1]
    if dims_row is None:
        dims_row = _dym_dims(cfg, slot, mf_dim)
    group_dim = dims_row if dims_row is not None else mf_dim
    create, mf_size, mf_touched = _mf_create(ws, cfg, touched, show, click,
                                             group_dim)
    mf = jnp.where(
        mf_touched[:, None],
        jnp.clip(ws["mf"] + cfg.mf_learning_rate *
                 acc["g_embedx"] / safe_scale[:, None],
                 cfg.mf_min_bound, cfg.mf_max_bound),
        ws["mf"])
    out = {"show": show, "click": click, "delta_score": delta,
           "slot": slot,
           "embed_w": embed_w, "embed_g2sum": ws["embed_g2sum"],
           "mf_size": mf_size, "mf_g2sum": ws["mf_g2sum"], "mf": mf}
    for extra in ("mf_ex", "mf_ex_g2sum"):
        if extra in ws:
            out[extra] = ws[extra]
    return out


def sparse_std_adagrad_apply(ws: Dict[str, jnp.ndarray],
                             acc: Dict[str, jnp.ndarray],
                             cfg: SparseSGDConfig,
                         dims_row=None) -> Dict[str, jnp.ndarray]:
    """StdAdaGradSGDRule (sparse_sgd_rule.h:109, UpdateValueWork in
    sparse_sgd_rule.cc): adagrad with a *per-dimension* g2sum for the embedx
    group (field mf_g2sum_d [N, D]) instead of the shared per-row scalar.
    The 1-dim lr weight is identical to plain adagrad."""
    touched, show, click, delta = _common_stats(ws, acc, cfg)
    slot = jnp.where(touched, acc["slot"], ws["slot"])
    lr_embed = jnp.where(slot == cfg.nodeid_slot, cfg.learning_rate,
                         cfg.feature_learning_rate)
    safe_scale = jnp.where(acc["g_show"] > 0, acc["g_show"], 1.0)
    ratio = lr_embed * jnp.sqrt(cfg.initial_g2sum /
                                (cfg.initial_g2sum + ws["embed_g2sum"]))
    sg = acc["g_embed"] / safe_scale
    embed_w = jnp.where(
        touched,
        jnp.clip(ws["embed_w"] + sg * ratio, cfg.min_bound, cfg.max_bound),
        ws["embed_w"])
    embed_g2sum = jnp.where(touched, ws["embed_g2sum"] + sg * sg,
                            ws["embed_g2sum"])

    mf_dim = ws["mf"].shape[1]
    if dims_row is None:
        dims_row = _dym_dims(cfg, slot, mf_dim)
    group_dim = dims_row if dims_row is not None else mf_dim
    create, mf_size, mf_touched = _mf_create(ws, cfg, touched, show, click,
                                             group_dim)
    sg_mf = acc["g_embedx"] / safe_scale[:, None]             # [N, D]
    ratio_d = cfg.mf_learning_rate * jnp.sqrt(
        cfg.mf_initial_g2sum / (cfg.mf_initial_g2sum + ws["mf_g2sum_d"]))
    mf = jnp.where(
        mf_touched[:, None],
        jnp.clip(ws["mf"] + sg_mf * ratio_d, cfg.mf_min_bound,
                 cfg.mf_max_bound),
        ws["mf"])
    mf_g2sum_d = jnp.where(mf_touched[:, None],
                           ws["mf_g2sum_d"] + sg_mf * sg_mf,
                           ws["mf_g2sum_d"])

    out = {"show": show, "click": click, "delta_score": delta, "slot": slot,
           "embed_w": embed_w, "embed_g2sum": embed_g2sum,
           "mf_size": mf_size, "mf_g2sum": ws["mf_g2sum"],
           "mf_g2sum_d": mf_g2sum_d, "mf": mf}
    for extra in ("mf_ex", "mf_ex_g2sum"):
        if extra in ws:
            out[extra] = ws[extra]
    return out


def sparse_adam_dim_apply(ws: Dict[str, jnp.ndarray],
                          acc: Dict[str, jnp.ndarray],
                          cfg: SparseSGDConfig,
                         dims_row=None) -> Dict[str, jnp.ndarray]:
    """Per-dimension SparseAdam (CPU SparseAdamSGDRule sparse_sgd_rule.h:126
    / GPU SparseAdamOptimizer optimizer.cuh.h:148): embedx keeps full [N, D]
    first/second moments (mf_gsum_d / mf_g2sum_d) with shared scalar
    beta-power trackers; the 1-dim lr weight uses the scalar moment fields
    (identical to the shared rule at dim 1)."""
    eps = cfg.ada_epsilon
    b1, b2 = cfg.beta1_decay_rate, cfg.beta2_decay_rate
    touched, show, click, delta = _common_stats(ws, acc, cfg)
    slot = jnp.where(touched, acc["slot"], ws["slot"])
    safe_scale = jnp.where(acc["g_show"] > 0, acc["g_show"], 1.0)

    embed_w, e_m1, e_m2, e_b1, e_b2 = _shared_adam_group(
        ws["embed_w"], ws["embed_gsum"], ws["embed_g2sum"],
        ws["embed_b1p"], ws["embed_b2p"], acc["g_embed"], acc["g_show"],
        cfg.learning_rate, b1, b2, cfg.mf_min_bound, cfg.mf_max_bound,
        touched, 1, eps)

    mf_dim = ws["mf"].shape[1]
    if dims_row is None:
        dims_row = _dym_dims(cfg, slot, mf_dim)
    group_dim = dims_row if dims_row is not None else mf_dim
    create, mf_size, mf_touched = _mf_create(ws, cfg, touched, show, click,
                                             group_dim)

    sg = acc["g_embedx"] / safe_scale[:, None]                # [N, D]
    new_m1 = b1 * ws["mf_gsum_d"] + (1 - b1) * sg
    new_m2 = b2 * ws["mf_g2sum_d"] + (1 - b2) * sg * sg
    lr_t = cfg.mf_learning_rate * jnp.sqrt(1.0 - ws["mf_b2p"]) \
        / (1.0 - ws["mf_b1p"])
    new_mf = jnp.clip(ws["mf"] + lr_t[:, None]
                      * (new_m1 / (jnp.sqrt(new_m2) + eps)),
                      cfg.mf_min_bound, cfg.mf_max_bound)
    mask = mf_touched[:, None]
    mf = jnp.where(mask, new_mf, ws["mf"])
    mf_gsum_d = jnp.where(mask, new_m1, ws["mf_gsum_d"])
    mf_g2sum_d = jnp.where(mask, new_m2, ws["mf_g2sum_d"])
    mf_b1p = jnp.where(mf_touched, ws["mf_b1p"] * b1, ws["mf_b1p"])
    mf_b2p = jnp.where(mf_touched, ws["mf_b2p"] * b2, ws["mf_b2p"])
    # rows created this push reset their beta powers to the decay rates
    # (creation init, optimizer.cuh.h:260-268)
    mf_b1p = jnp.where(create, b1, mf_b1p)
    mf_b2p = jnp.where(create, b2, mf_b2p)

    out = {"show": show, "click": click, "delta_score": delta,
           "slot": slot,
           "embed_w": embed_w, "embed_gsum": e_m1, "embed_g2sum": e_m2,
           "embed_b1p": e_b1, "embed_b2p": e_b2,
           "mf_size": mf_size, "mf": mf,
           "mf_gsum_d": mf_gsum_d, "mf_g2sum_d": mf_g2sum_d,
           "mf_gsum": ws["mf_gsum"], "mf_g2sum": ws["mf_g2sum"],
           "mf_b1p": mf_b1p, "mf_b2p": mf_b2p}
    for extra in ("mf_ex", "mf_ex_g2sum"):
        if extra in ws:
            out[extra] = ws[extra]
    return out


OPTIMIZERS = {
    "adagrad": sparse_adagrad_apply,
    "shared_adam": sparse_adam_apply,
    "adam": sparse_adam_dim_apply,
    "std_adagrad": sparse_std_adagrad_apply,
    "naive": sparse_naive_apply,
}


def apply_push(ws, acc, cfg: SparseSGDConfig, dims_row=None):
    """dims_row: optional per-row [N] mf dims (dynamic-dim accessor,
    ≙ CtrDymfAccessor) — rules divide/mask by the row's true width.

    Row-count generic: every rule is elementwise over axis 0, so nothing
    here may assume ws spans the whole pass."""
    out = OPTIMIZERS[cfg.optimizer](ws, acc, cfg, dims_row)
    # ctr_double accessor support: exact pass-delta counters ride along —
    # small magnitudes, so the f32 adds are exact even when the absolute
    # show has outgrown f32's integer range; end_pass merges them into the
    # host's f64 stats (≙ DownpourCtrDoubleAccessor's double update)
    if "show_acc" in ws:
        touched = push_touched(ws, acc)
        out["show_acc"] = jnp.where(touched, ws["show_acc"] + acc["g_show"],
                                    ws["show_acc"])
        out["click_acc"] = jnp.where(
            touched, ws["click_acc"] + acc["g_click"], ws["click_acc"])
    return out
