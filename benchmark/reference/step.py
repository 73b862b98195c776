"""The plain reference of one training step, independent of the program.

float32 ``jax.numpy``, no kernels, no plans, no packing tricks: a dense
gather by row index, a masked sum over a slot's keys, the CVM transform,
the configuration's tower (``reference/<config>.py``), and the two
optimizers written out from their equations.  It is fed the program's
initial rows and parameters as arrays and the first batches of the feed
the program trains, and returns the loss of each step.  ``correct``
compares those with the program's own.

Equations and where they come from:

* pull: ``(show, click, embed_w, embedx * [mf_size > 0])`` of each key
  (PaddleBox ``CommonPullValue``, feature_value.h: embedx is hidden until
  created).
* pool + CVM (``fused_seqpool_cvm``): sum over a slot's keys, then
  ``show' = log(show + 1)``, ``click' = log(click + 1) - log(show + 1)``.
* loss: mean over the batch of the sigmoid cross-entropy of the logit.
* push (``PushSparseGradCaseGPU``): every key occurrence carries
  ``g_show = 1``, ``g_click = label`` and the loss gradient of its
  slot's pooled ``embed_w`` and ``embedx``; occurrences of one row add.
* sparse adagrad (heter_ps ``optimizer.cuh.h``, ``dy_mf_update_value``):
  with ``g`` the summed gradient over ``g_show``,
  ``w += lr * sqrt(g0 / (g0 + g2sum)) * g`` clipped to the bounds,
  ``g2sum += mean(g^2)``; embedx is created, not updated, on the push
  that takes ``nonclk * (show - click) + clk * click`` over the
  threshold.
* dense Adam (Kingma & Ba, the program's ``optax.adam(1e-3)``):
  ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 - b2) g^2``,
  ``p -= lr * (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)``.

It runs on the host's CPU device, where float32 matmuls are float32 (on a
TPU they would need ``highest``; the context manager is set all the
same), so it costs the chip no memory and compiles in seconds.

The towers take their matrix product from ``matmul(mode)``.  The program
keeps float32 parameters and activations and multiplies them at the
device's default precision, which on a TPU rounds both operands to
bfloat16 and accumulates in float32, in the backward products as in the
forward one.  ``bf16_operands`` does the same rounding here, so that the
gate on the losses need not leave room for it; ``float32`` multiplies
what it is given.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# How close the program has to come is the configuration's
# (``correct.loss_rtol`` with its reason, in ``configs/<config>.json``).
STEPS = 3

ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
ROW_FIELDS = ("show", "click", "embed_w", "embed_g2sum", "mf_size",
              "mf_g2sum", "mf")


def matmul(mode: str) -> Callable:
    """``a @ b`` for 2-D float32 ``a``, ``b`` as ``mode`` says (above)."""
    if mode == "float32":
        return jnp.matmul
    if mode != "bf16_operands":
        raise ValueError(f"unknown reference_matmul {mode!r}")

    def r(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    @jax.custom_vjp
    def mm(a, b):
        return r(a) @ r(b)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(saved, g):
        a, b = saved
        return r(g) @ r(b).T, r(a).T @ r(g)

    mm.defvjp(fwd, bwd)
    return mm


def _pull_pool(rows: Dict[str, jnp.ndarray], idx, mask):
    """idx, mask [S, L, B] -> pooled [B, S, 3 + D]."""
    m = mask.astype(jnp.float32)
    created = (rows["mf_size"][idx] > 0).astype(jnp.float32) * m
    show = jnp.sum(rows["show"][idx] * m, axis=1)            # [S, B]
    click = jnp.sum(rows["click"][idx] * m, axis=1)
    embed_w = jnp.sum(rows["embed_w"][idx] * m, axis=1)
    embedx = jnp.sum(rows["mf"][idx] * created[..., None], axis=1)
    show_t = jnp.log(show + 1.0)
    click_t = jnp.log(click + 1.0) - show_t
    pooled = jnp.concatenate(
        [jnp.stack([show_t, click_t, embed_w], axis=-1), embedx], axis=-1)
    return jnp.transpose(pooled, (1, 0, 2))


def _loss(logits, labels, valid):
    per = (jnp.maximum(logits, 0.0) - logits * labels
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    w = valid.astype(jnp.float32)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def _push_adagrad(rows, idx, mask, labels, d_pooled, sgd):
    """One merged push and the sparse adagrad rule over every row."""
    n = rows["show"].shape[0]
    s, l, b = idx.shape
    flat = idx.reshape(-1)
    m = mask.astype(jnp.float32).reshape(-1)

    def per_row(values):                  # [S, L, B(, D)] -> [n(, D)]
        v = values.reshape((s * l * b,) + values.shape[3:])
        v = v * (m if v.ndim == 1 else m[:, None])
        return jax.ops.segment_sum(v, flat, num_segments=n)

    ones = jnp.ones((s, l, b), jnp.float32)
    g_show = per_row(ones)
    g_click = per_row(ones * labels[None, None, :])
    d_slb = jnp.transpose(d_pooled, (1, 0, 2))[:, None]      # [S,1,B,3+D]
    g_embed = per_row(jnp.broadcast_to(d_slb[..., 2], (s, l, b)))
    g_embedx = per_row(jnp.broadcast_to(
        d_slb[..., 3:], (s, l, b, d_pooled.shape[-1] - 3)))

    touched = (g_show > 0) & (jnp.arange(n) != 0)
    scale = jnp.where(g_show > 0, g_show, 1.0)
    show = jnp.where(touched, rows["show"] + g_show, rows["show"])
    click = jnp.where(touched, rows["click"] + g_click, rows["click"])

    g = g_embed / scale
    ratio = sgd["feature_learning_rate"] * jnp.sqrt(
        sgd["initial_g2sum"] / (sgd["initial_g2sum"] + rows["embed_g2sum"]))
    embed_w = jnp.where(
        touched, jnp.clip(rows["embed_w"] + g * ratio, sgd["min_bound"],
                          sgd["max_bound"]), rows["embed_w"])
    embed_g2sum = jnp.where(touched, rows["embed_g2sum"] + g * g,
                            rows["embed_g2sum"])

    dim = rows["mf"].shape[1]
    score = (sgd["nonclk_coeff"] * (show - click)
             + sgd["clk_coeff"] * click)
    had = rows["mf_size"] > 0
    create = touched & ~had & (score >= sgd["mf_create_thresholds"])
    mf_size = jnp.where(create, dim, rows["mf_size"])
    update = touched & had
    gx = g_embedx / scale[:, None]
    ratio_x = sgd["mf_learning_rate"] * jnp.sqrt(
        sgd["mf_initial_g2sum"]
        / (sgd["mf_initial_g2sum"] + rows["mf_g2sum"]))
    mf = jnp.where(
        update[:, None],
        jnp.clip(rows["mf"] + gx * ratio_x[:, None], sgd["mf_min_bound"],
                 sgd["mf_max_bound"]), rows["mf"])
    mf_g2sum = jnp.where(update,
                         rows["mf_g2sum"] + jnp.sum(gx * gx, axis=1) / dim,
                         rows["mf_g2sum"])
    return {"show": show, "click": click, "embed_w": embed_w,
            "embed_g2sum": embed_g2sum, "mf_size": mf_size,
            "mf_g2sum": mf_g2sum, "mf": mf}


def _adam(params, m, v, grads, t):
    b1, b2, lr, eps = ADAM["b1"], ADAM["b2"], ADAM["lr"], ADAM["eps"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, a, c: p - lr * (a / (1 - b1 ** t))
        / (jnp.sqrt(c / (1 - b2 ** t)) + eps), params, m, v)
    return params, m, v


def make_step(logit: Callable, sgd: Dict[str, float],
              mode: str = "float32"):
    """The jitted reference step for a tower ``logit(params, pooled,
    dense, mm)`` with pooled [B, S, 3 + D] and ``mm = matmul(mode)``."""
    mm = matmul(mode)

    def step(rows, params, m, v, t, idx, lengths, dense, labels, valid):
        mask = jnp.arange(idx.shape[1])[None, :, None] < lengths[:, None, :]
        idx = jnp.where(mask, idx, 0)
        pooled = _pull_pool(rows, idx, mask)

        def loss_fn(p, x):
            return _loss(logit(p, x, dense, mm), labels, valid)

        loss, (d_params, d_pooled) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(params, pooled)
        rows = _push_adagrad(rows, idx, mask, labels, d_pooled, sgd)
        params, m, v = _adam(params, m, v, d_params, t)
        return rows, params, m, v, loss

    return jax.jit(step)


def losses(logit: Callable, sgd: Dict[str, float], rows: Dict, params,
           batches: Dict[str, np.ndarray], steps: int = STEPS,
           mode: str = "float32") -> List[float]:
    """Per-step loss of ``steps`` reference steps from the given state.
    ``rows``: the working set's fields [n(, D)]; ``batches``: the feed's
    ``indices`` [N, S, L, B], ``lengths`` [N, S, B], ``dense`` [N, B, D],
    ``labels`` [N, B], ``valid`` [N, B] with N >= steps."""
    cpu = jax.devices("cpu")[0]
    put = lambda tree: jax.device_put(  # noqa: E731
        jax.tree.map(np.asarray, tree), cpu)
    rows = put({f: rows[f] for f in ROW_FIELDS})
    params = put(params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = make_step(logit, sgd, mode)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            b = put({k: batches[k][i] for k in
                     ("indices", "lengths", "dense", "labels", "valid")})
            rows, params, m, v, loss = step(
                rows, params, m, v, np.float32(i + 1), b["indices"],
                b["lengths"], b["dense"], b["labels"], b["valid"])
            out.append(float(loss))
    return out
