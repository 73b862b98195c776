"""The plain reference of the looped-language-model step, independent of
``paddlebox_tpu/models/looplm.py``.

Ouro ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; config.json of ByteDance/Ouro-2.6B) as the configuration
file states it.  One sequence at a time, one head of attention at a time
through ``vmap``, Python loops over recurrent steps, layers and token
blocks; float32 ``jax.numpy``; every matrix product through ``mm``
(``reference/step.py``: operands rounded to bfloat16 where the program
runs on a TPU, plain float32 elsewhere).  No kernel, no scan, no batching.

    RMS(z; g) = g * z / sqrt(mean(z^2) + eps)
                (an all-zero z passes no gradient, see ``rms``)

    layer l on h [n, H]  (sandwich normalisation):
      a  = RMS(h; g1)
      q, k, v = a Wq, a Wk, a Wv              each [n, heads, head_dim]
      q, k = rope(q, i), rope(k, i)           rotate-half, theta, position i
      o  = softmax(q k^T / sqrt(head_dim) + causal) v     keys j <= i, j < len
      h1 = h + RMS(o Wo; g2)
      b  = RMS(h1; g3)
      h2 = h1 + RMS((silu(b Wg) * (b Wu)) Wd; g4)

    recurrent step t = 1..T, the same layers each time:
      h(0) = x;  h(t) = RMS(layers(h(t-1)); gf)
      z(t) = h(t) W_head;  lam_t = sigmoid(h(t) . w_gate + b_gate)

    exit distribution:  p_1 = lam_1,  p_t = lam_t prod_{j<t} (1 - lam_j),
                        p_T = prod_{j<T} (1 - lam_j)
    ce_t,i = -log softmax(z(t)_i)[y_i],  y_i = token_{i+1}
    loss = mean over target positions of [sum_t p_t ce_t - beta H(p)]

The rest of the step is the system's: ``x_i`` is the token's ``mf`` row
times its created mask; the rows take ``reference/step.py``'s sparse rule
(imported, every occurrence its own gradient, ``embed_w`` none); the
dense parameters Adam.  Two units are jitted because they repeat (a layer
is applied layers x T times a sequence, a head block tokens / block
times) and a layer is under ``jax.checkpoint`` so that a sequence's
backward keeps one activation a layer application: management of compile
time and memory, not of the arithmetic.

The parameter tree is the program's: ``layers`` holds each weight stacked
over layers (``wq wk wv wo wg wu wd g1 g2 g3 g4``), then ``gf``, ``head``,
``gate_w``, ``gate_b``.  The reference works on it unstacked (``unstack``:
``layers`` a list of one dict a layer), so that a layer's gradient has a
layer's size, and gives gradients back in that form (``restack``).
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import step as reference

HEAD_BLOCK = 1024


def sizes(cfg: dict) -> dict:
    """What the equations need of a configuration file."""
    return {"heads": int(cfg["num_attention_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "layers": int(cfg["num_hidden_layers"]),
            "steps": int(cfg["total_ut_steps"]),
            "vocab": int(cfg["vocab_size"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "beta": float(cfg["loss"]["beta"]),
            "neg_seed": int(cfg["loss"]["negative_seed"])}


def unstack(params: dict) -> dict:
    """The program's tree with ``layers`` as a list of per-layer dicts (a
    copy of the layers' weights); a tree already in that form is returned
    as it is."""
    if isinstance(params["layers"], list):
        return params
    stacked = params["layers"]
    n = next(iter(stacked.values())).shape[0]
    return {**params,
            "layers": [{k: v[l] for k, v in stacked.items()}
                       for l in range(n)]}


def restack(params: dict) -> dict:
    """``unstack``'s inverse."""
    if not isinstance(params["layers"], list):
        return params
    return {**params, "layers": {
        k: jnp.stack([w[k] for w in params["layers"]])
        for k in params["layers"][0]}}


def host_leaves(params: dict) -> Dict[str, np.ndarray]:
    """A parameter tree of either form on the host, float32, by leaf of
    the program's tree: ``gf`` ..., ``layers.wq`` [layers, ...] ..."""
    def host(a):
        return np.asarray(a.astype(jnp.float32))

    out = {k: host(v) for k, v in params.items() if k != "layers"}
    layers = params["layers"]
    for k in (layers[0] if isinstance(layers, list) else layers):
        out["layers." + k] = np.stack([host(w[k]) for w in layers]) \
            if isinstance(layers, list) else host(layers[k])
    return out


def rms(z, g, eps):
    """An all-zero z (a row not created yet) is a constant to the
    gradient: its Jacobian g / sqrt(eps) = 1000 g, chained norm after
    norm down a position that stays zero, leaves float32 (``assumed``)."""
    zero = jnp.all(z == 0, axis=-1, keepdims=True)
    z = jnp.where(zero, jax.lax.stop_gradient(z), z)
    return g * z / jnp.sqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps)


def rotate(x, theta):
    """x [n, heads, head_dim], position = row index; rotate-half form:
    pairs (x_j, x_{j + d/2}) turn by i * theta^(-2j/d)."""
    n, _, d = x.shape
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(n, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), x.dtype)[:, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "theta",
                                             "eps", "mode"))
def layer(w, h, length, *, heads, head_dim, theta, eps, mode):
    """One layer on one sequence h [n, H] of ``length`` valid tokens."""
    mm = reference.matmul(mode)
    n = h.shape[0]
    a = rms(h, w["g1"], eps)
    q = rotate(mm(a, w["wq"]).reshape(n, heads, head_dim), theta)
    k = rotate(mm(a, w["wk"]).reshape(n, heads, head_dim), theta)
    v = mm(a, w["wv"]).reshape(n, heads, head_dim)
    qh, kh, vh = (jnp.transpose(t, (1, 0, 2)) for t in (q, k, v))
    scores = jax.vmap(mm)(qh, jnp.transpose(kh, (0, 2, 1))) \
        / math.sqrt(head_dim)                              # [heads, n, n]
    i = jnp.arange(n)
    allowed = (i[None, :] <= i[:, None]) & (i[None, :] < length)
    scores = jnp.where(allowed[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.transpose(jax.vmap(mm)(probs, vh), (1, 0, 2)).reshape(n, -1)
    h1 = h + rms(mm(o, w["wo"]), w["g2"], eps)
    b = rms(h1, w["g3"], eps)
    ffn = mm(jax.nn.silu(mm(b, w["wg"])) * mm(b, w["wu"]), w["wd"])
    return h1 + rms(ffn, w["g4"], eps)


@functools.partial(jax.jit, static_argnames=("mode",))
def head_block(head, gate_w, gate_b, hb, targets, negatives, *, mode):
    """A block of positions of one recurrent step: cross-entropy of the
    target, the gate's logit, log p of target and of negative."""
    z = reference.matmul(mode)(hb, head)                   # [m, V]
    lse = jax.nn.logsumexp(z, axis=-1)
    zy = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    zn = jnp.take_along_axis(z, negatives[:, None], axis=1)[:, 0]
    gate = jnp.sum(hb * gate_w[None, :], axis=-1) + gate_b   # float32
    return lse - zy, gate, zy - lse, zn - lse


def negatives_of(seed: int, place: int, first_key: int, length: int, n: int,
                 vocab: int) -> np.ndarray:
    """The sampled negative of each position of one example: a counter
    hash of (seed, example, position), the example named by its place in
    the batch, its first key and its length.  uint32 arithmetic."""
    def mix(h):
        h = (h ^ (h >> np.uint32(16))) * np.uint32(0x7FEB352D)
        h = (h ^ (h >> np.uint32(15))) * np.uint32(0x846CA68B)
        return h ^ (h >> np.uint32(16))

    with np.errstate(over="ignore"):
        ex = mix(np.uint32(seed & 0xFFFFFFFF)
                 + np.uint32(place) * np.uint32(0x9E3779B1)
                 + np.uint32(first_key & 0xFFFFFFFF) * np.uint32(0x85EBCA77)
                 + np.uint32(length) * np.uint32(0xC2B2AE3D))
        h = mix(ex + np.arange(n, dtype=np.uint32) * np.uint32(0x27D4EB2F))
    return (h % np.uint32(vocab)).astype(np.int32)


def hidden_states(params, x, length, sz: dict, mode: str) -> list:
    """h(1), ..., h(T) of one sequence x [n, H]."""
    kw = {k: sz[k] for k in ("heads", "head_dim", "theta", "eps")}
    one_layer = jax.checkpoint(functools.partial(layer, mode=mode, **kw))
    layers = unstack(params)["layers"]
    assert len(layers) == sz["layers"]
    h, out = x, []
    for _ in range(sz["steps"]):
        for w in layers:
            h = one_layer(w, h, length)
        h = rms(h, params["gf"], sz["eps"])
        out.append(h)
    return out


def sequence_terms(params, x, targets, negatives, length, sz: dict,
                   mode: str):
    """One sequence x [n, H] -> ce [T, n], gate logits [T, n], and the
    last step's log p of target and negative [n]."""
    n = x.shape[0]
    ce, gate = [], []
    block = jax.checkpoint(functools.partial(head_block, mode=mode))
    for h in hidden_states(params, x, length, sz, mode):
        parts = [block(params["head"], params["gate_w"],
                       params["gate_b"], h[lo:lo + HEAD_BLOCK],
                       targets[lo:lo + HEAD_BLOCK],
                       negatives[lo:lo + HEAD_BLOCK])
                 for lo in range(0, n, HEAD_BLOCK)]
        c, g, lp_pos, lp_neg = (jnp.concatenate(p) for p in zip(*parts))
        ce.append(c)
        gate.append(g)
    return jnp.stack(ce), jnp.stack(gate), lp_pos, lp_neg


def exit_distribution(gate):
    """gate logits [T, n] -> p [T, n]."""
    lam = jax.nn.sigmoid(gate)
    p, stay = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p + [stay])


def sequence_loss_sum(params, x, tokens, length, place, first_key, sz: dict,
                      mode: str):
    """Sum over one sequence's target positions of
    ``sum_t p_t ce_t - beta H(p)``; aux: the AUC's scores of positives and
    negatives, the expected exit step's sum, and the per-step terms."""
    n = x.shape[0]
    length = int(length)
    targets = np.zeros(n, np.int32)
    targets[:n - 1] = tokens[1:]
    negatives = negatives_of(sz["neg_seed"], place, first_key, length, n,
                             sz["vocab"])
    ce, gate, lp_pos, lp_neg = sequence_terms(
        params, x, jnp.asarray(targets), jnp.asarray(negatives), length, sz,
        mode)
    p = exit_distribution(gate)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-38)),
                                 0.0), axis=0)
    per = jnp.sum(p * ce, axis=0) - sz["beta"] * entropy
    has_target = jnp.arange(n) < length - 1
    ln_v = math.log(sz["vocab"])
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None]
    # every entry has the sequence's padded shape, whatever its length
    # (a shape a length would compile a program a length): the AUC's
    # pairs are the positions where has_target holds
    aux = {"pos": jax.nn.sigmoid(lp_pos + ln_v),
           "neg": jax.nn.sigmoid(lp_neg + ln_v), "has_target": has_target,
           "exit_sum": jnp.sum(jnp.where(has_target,
                                         jnp.sum(p * steps, axis=0), 0.0)),
           "ce": ce, "gate": gate, "p": p}
    return jnp.sum(jnp.where(has_target, per, 0.0)), aux


def rows_and_lengths(batch: Dict[str, np.ndarray]):
    """A feed batch's working-set rows [L, B] and lengths [B] (0 for an
    example that only pads the batch)."""
    return (np.asarray(batch["indices"])[0],
            np.where(np.asarray(batch["valid"]),
                     np.asarray(batch["lengths"])[0], 0))


def batch_loss(params, mf_masked, batch, cfg: dict, mode: str,
               with_grads: bool = False, add=None):
    """Mean loss of one feed batch; with ``with_grads`` also the gradient
    of that mean to the parameters (unstacked, ``restack`` gives the
    program's form) and, per occurrence [L, B, D], to the pulled rows.
    ``mf_masked`` [rows, D]: the working set's ``mf`` times its created
    mask.  ``add(total, g)`` sums the sequences' gradient trees (the chip
    run hands one that reuses ``total``'s memory)."""
    add = add or (lambda a, b: jax.tree.map(jnp.add, a, b))
    params = unstack(params)
    sz = sizes(cfg)
    idx, lengths = rows_and_lengths(batch)
    keys = np.asarray(batch["seq_keys"])                   # [B, L]
    tokens = np.clip(keys - int(cfg["loss"]["key_base"]), 0,
                     sz["vocab"] - 1)
    count = int(np.maximum(lengths - 1, 0).sum())
    scale = 1.0 / max(count, 1)
    total, g_params, aux_all = 0.0, None, []
    g_rows = np.zeros(idx.shape + (mf_masked.shape[1],), np.float32) \
        if with_grads else None
    for b in range(idx.shape[1]):
        if lengths[b] <= 0:
            continue
        pos = jnp.arange(idx.shape[0]) < lengths[b]
        x = jnp.where(pos[:, None], mf_masked[jnp.asarray(idx[:, b])], 0.0)
        args = (tokens[b], lengths[b], b, int(keys[b, 0]), sz, mode)
        if with_grads:
            (s, aux), (gp, gx) = jax.value_and_grad(
                sequence_loss_sum, argnums=(0, 1), has_aux=True)(
                    params, x, *args)
            g_params = gp if g_params is None else add(g_params, gp)
            g_rows[:, b] = np.asarray(gx) * scale
            # or the next sequence's backward runs beside this one's
            # whole gradient tree, 2 GB of the chip's 16 at Ouro's widths
            del gp, gx
        else:
            s, aux = sequence_loss_sum(params, x, *args)
        total += float(s)
        aux_all.append(aux)
    out = {"loss": total * scale, "targets": count, "aux": aux_all}
    if with_grads:
        out["d_params"] = jax.tree.map(lambda g: g * scale, g_params)
        out["d_rows"] = g_rows
    return out


def created_mf(rows: Dict) -> jnp.ndarray:
    return jnp.asarray(rows["mf"]) * (jnp.asarray(rows["mf_size"]) > 0
                                      )[:, None].astype(jnp.float32)


def push_rows(rows: Dict, batch, d_rows: np.ndarray, sgd: dict) -> Dict:
    """``reference/step.py``'s row rule on a batch whose gradient belongs
    to each occurrence: every position is handed over as an example of
    its own with one key (g_show 1, g_click its example's label), the
    ``embed_w`` column's gradient zero."""
    idx, lengths = rows_and_lengths(batch)
    l, b = idx.shape
    mask = (np.arange(l)[:, None] < lengths[None, :]).reshape(1, 1, l * b)
    flat = np.where(mask, idx.reshape(1, 1, l * b), 0)
    labels = np.tile(np.asarray(batch["labels"], np.float32), l)
    d = np.zeros((l * b, 1, 3 + d_rows.shape[-1]), np.float32)
    d[:, 0, 3:] = d_rows.reshape(l * b, -1)
    return reference._push_adagrad(
        {f: jnp.asarray(rows[f]) for f in reference.ROW_FIELDS},
        jnp.asarray(flat), jnp.asarray(mask), jnp.asarray(labels),
        jnp.asarray(d), sgd)


@jax.jit
def adam_leaf(p, m, v, g, t):
    """One leaf of Adam's update from given moments; returns the new
    parameter only (the reference keeps no moments of its own)."""
    c = reference.ADAM
    m = c["b1"] * m + (1 - c["b1"]) * g
    v = c["b2"] * v + (1 - c["b2"]) * g * g
    return p - c["lr"] * (m / (1 - c["b1"] ** t)) / (
        jnp.sqrt(v / (1 - c["b2"] ** t)) + c["eps"])


@jax.jit
def sq_dist(a, b):
    """|a - b|^2 of two arrays of one shape, in float32."""
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sum(d * d)


def leaf_sq_dist(new: dict, old: dict) -> Dict[str, float]:
    """|new - old|^2 by leaf of the program's tree (a stacked leaf is the
    sum over its layers), for two unstacked trees."""
    out = {k: float(sq_dist(new[k], old[k])) for k in new if k != "layers"}
    for w_new, w_old in zip(new["layers"], old["layers"]):
        for k in w_new:
            out["layers." + k] = out.get("layers." + k, 0.0) + float(
                sq_dist(w_new[k], w_old[k]))
    return out


def adam_unstacked(params: dict, mu: dict, nu: dict, grads: dict, t):
    """Adam's new parameters, leaf by leaf, for unstacked ``params`` and
    ``grads`` from the program's (stacked) moments: a layer's moments are
    sliced out as its leaf is updated, never the whole tree."""
    new = {k: adam_leaf(params[k], mu[k], nu[k], grads[k], t)
           for k in params if k != "layers"}
    new["layers"] = [
        {k: adam_leaf(w[k], mu["layers"][k][l], nu["layers"][k][l], g[k], t)
         for k in w}
        for l, (w, g) in enumerate(zip(params["layers"], grads["layers"]))]
    return new


def step(rows, params, m, v, t, batch, cfg, mode="float32"):
    """The whole plain step from a given state: returns the new rows,
    parameters and moments, the loss and the AUC pairs."""
    out = batch_loss(params, created_mf(rows), batch, cfg, mode,
                     with_grads=True)
    rows = push_rows(rows, batch, out["d_rows"], cfg["table"]["sgd"])
    params, m, v = reference._adam(params, m, v, restack(out["d_params"]),
                                   np.float32(t))
    return rows, params, m, v, out
