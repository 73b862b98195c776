"""The plain reference of the hybrid linear-attention step, independent of
``paddlebox_tpu/models/hybridlm.py`` and ``parallel/moe.py``.

Kimi Linear ("Kimi Linear: An Expressive, Efficient Attention
Architecture", arXiv:2510.26692; config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct) as the configuration file states
it and cuts it.  One sequence at a time; float32 ``jax.numpy``; every
projection through ``mm`` (``reference/step.py``: operands rounded to
bfloat16 where the program runs on a TPU, plain float32 elsewhere); the
delta rule **token by token**, attention as a full softmax one head at a
time, the experts as a loop over the held ones with a mask.  No chunked
algebra, no sorting, no grouped product.

    RMS(z; g) = g * z / sqrt(mean(z^2) + eps)   (an all-zero z passes no
                                                  gradient: ``ouro.rms``)
    layer l on h [n, H]:  a = RMS(h; g1);  h' = h + mixer_l(a)
                          b = RMS(h'; g2); h'' = h' + ffn_l(b)
    out = RMS(h_L; gf) W_head

    KDA (heads x d):
      q, k, v = silu(conv(a Wq)), silu(conv(a Wk)), silu(conv(a Wv))
                conv(z)_t = sum_{j < kernel} c_j * z_{t-j}
      q, k <- q / |q| * d^-1/2, k / |k|            |z| = sqrt(z.z + 1e-6)
      g_t = -exp(A_log) * softplus((a Wf1) Wf2 + dt_bias);  alpha_t = e^g_t
      beta_t = sigmoid(a Wb)
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
      mixer = [RMS_head(o_t; g_o) * sigmoid((a Wg1) Wg2)] Wo
    MLA (no rotation):
      q_t = a Wq;  [c_t ; kr_t] = a Wkva
      [kc_t,h ; v_t,h] = RMS(c_t; g_c) Wkvb
      o = softmax(q [kc ; kr]^T / sqrt(nope + rope) + causal, j < len) v
      mixer = o Wo
    dense ffn: (silu(b Wg) * (b Wu)) Wd
    routed ffn: s = sigmoid(b Wr) over all ``published.num_experts``
      (float32: no rounding decides who is eighth);  chosen = the top-k
      of s + bias;  w_e = scale * s_e / sum_chosen s
      ffn = sum over e chosen AND held of w_e E_e(b) + E_shared(b)
    loss = mean over target positions of -log softmax(out_i)[token_{i+1}]

The share (``share`` in the configuration file): this chip holds
``num_experts`` experts of each routed layer, ids ``expert_first`` on;
what the other experts would add is left out, here as in the program.

The recurrence keeps a [heads, d, d] state a sequence (2.1 MB at the
published sizes); a scan over ``SCAN_BLOCK`` tokens under a checkpoint
inside a scan over blocks keeps a state a block and, in the backward,
the states of one block.  A sequence's backward goes layer by layer from
the kept layer inputs, each layer's parameter gradient added to the
batch's as it is made.  Both are management of memory, not of the
arithmetic, like the jitted layers.

The parameter tree is the program's: ``layers`` a list of one dict a
layer (``g1 g2 mixer{...} ffn{...}``), ``gf``, ``head``.  The interface is
the one ``generators/seq_epochs.py::LoopReferenceCheck`` calls.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import step as reference
from benchmark.reference.ouro_2p6b import (adam_leaf, created_mf,    # noqa
                                           negatives_of, push_rows, rms,
                                           rows_and_lengths, sq_dist)

HEAD_BLOCK = 1024
SCAN_BLOCK = 64


def sizes(cfg: dict) -> dict:
    """What the equations need of a configuration file."""
    lin = cfg["linear_attn_config"]
    n = int(cfg["num_hidden_layers"])
    first = int(cfg["share"]["expert_first"])
    return {
        "layers": tuple(
            ("kda" if l in lin["kda_layers"] else "mla",
             "dense" if l <= int(cfg["first_k_dense_replace"]) else "moe")
            for l in range(1, n + 1)),
        "kda_heads": int(lin["num_heads"]), "kda_dim": int(lin["head_dim"]),
        "mla_heads": int(cfg["num_attention_heads"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "held": tuple(range(first, first + int(cfg["num_experts"]))),
        "top_k": int(cfg["num_experts_per_token"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "vocab": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "neg_seed": int(cfg["loss"]["negative_seed"])}


def unstack(params: dict) -> dict:
    """The program's tree is a list of layers already."""
    return params


def named_leaves(params: dict) -> Dict[str, jnp.ndarray]:
    """``gf``, ``head``, ``layers.0.g1``, ``layers.0.mixer.wq`` ..."""
    out = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}.{k}" if prefix else k, v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(f"{prefix}.{i}", v)
        else:
            out[prefix] = node

    walk("", params)
    return out


def host_leaves(params: dict) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v.astype(jnp.float32))
            for k, v in named_leaves(params).items()}


@jax.jit
def _tree_sq_dist(new: dict, old: dict):
    return jax.tree.map(sq_dist, new, old)


def leaf_sq_dist(new: dict, old: dict) -> Dict[str, float]:
    """|new - old|^2 by leaf (one program for the tree, not one a leaf
    shape: a compile costs more than the sum)."""
    return {k: float(v)
            for k, v in named_leaves(_tree_sq_dist(new, old)).items()}


@jax.jit
def adam_unstacked(params: dict, mu: dict, nu: dict, grads: dict, t):
    """Adam's new parameters, leaf by leaf, from the program's moments."""
    return jax.tree.map(lambda p, m, v, g: adam_leaf(p, m, v, g, t),
                        params, mu, nu, grads)


# -- the layers, one sequence -----------------------------------------------

def conv(z, c):
    """conv(z)_t = sum_j c[j] * z_{t-j} on z [n, D], c [kernel, D]."""
    out = c[0] * z
    for j in range(1, c.shape[0]):
        out = out + c[j] * jnp.concatenate(
            [jnp.zeros_like(z[:j]), z[:-j]], axis=0)
    return out


def unit(z):
    return z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k, g [n, heads, d], v [n, heads,
    dv], beta [n, heads] -> o [n, heads, dv]."""
    n, nh, d = q.shape
    pad = -n % SCAN_BLOCK
    if pad:     # tokens past the end write nothing that is read
        q, k, v, g = (jnp.concatenate(
            [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)]) for t in
            (q, k, v, g))
        beta = jnp.concatenate([beta, jnp.zeros((pad, nh), beta.dtype)])

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, :, None] * state           # Diag(alpha) S
        seen = jnp.sum(state * k_t[:, :, None], axis=1)    # S^T k
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(t.reshape((-1, SCAN_BLOCK) + t.shape[1:])
               for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((nh, d, v.shape[-1]), q.dtype), xs)
    return o.reshape((-1,) + o.shape[2:])[:n]


def kda(w, a, sz, mm):
    n = a.shape[0]
    nh, d = sz["kda_heads"], sz["kda_dim"]

    def heads(z):
        return z.reshape(n, nh, d)

    q = unit(heads(jax.nn.silu(conv(mm(a, w["wq"]), w["cq"])))) * d ** -0.5
    k = unit(heads(jax.nn.silu(conv(mm(a, w["wk"]), w["ck"]))))
    v = heads(jax.nn.silu(conv(mm(a, w["wv"]), w["cv"])))
    g = -jnp.exp(w["a_log"])[:, None] * heads(jax.nn.softplus(
        mm(mm(a, w["wf1"]), w["wf2"]) + w["dt_bias"]))
    beta = jax.nn.sigmoid(mm(a, w["wb"]))
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid(heads(mm(mm(a, w["wg1"]), w["wg2"])))
    return mm((rms(o, w["g_o"], sz["eps"]) * gate).reshape(n, nh * d),
              w["wo"])


def mla(w, a, length, sz, mm):
    n = a.shape[0]
    nh, dn, dr, dv = sz["mla_heads"], sz["nope"], sz["rope"], sz["v_dim"]
    q = mm(a, w["wq"]).reshape(n, nh, dn + dr)
    ckv = mm(a, w["wkva"])
    c, kr = ckv[:, :sz["kv_rank"]], ckv[:, sz["kv_rank"]:]
    kv = mm(rms(c, w["g_c"], sz["eps"]), w["wkvb"]).reshape(n, nh, dn + dv)
    i = jnp.arange(n)
    allowed = (i[None, :] <= i[:, None]) & (i[None, :] < length)

    @jax.checkpoint
    def head(args):
        q_h, kc_h, v_h = args                  # [n, dn + dr], [n, dn], [n, dv]
        k_h = jnp.concatenate([kc_h, kr], axis=-1)
        scores = mm(q_h, k_h.T) / math.sqrt(dn + dr)
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        return mm(probs, v_h)

    o = jax.lax.map(head, tuple(jnp.transpose(t, (1, 0, 2)) for t in
                                (q, kv[..., :dn], kv[..., dn:])))
    return mm(jnp.transpose(o, (1, 0, 2)).reshape(n, nh * dv), w["wo"])


def swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def routed(w, b, sz, mm):
    """The held experts' part, expert by expert over every token, with a
    mask; then the shared expert."""
    s = jax.nn.sigmoid(jnp.matmul(b.astype(jnp.float32),
                                  w["router"].astype(jnp.float32)))
    order = jnp.argsort(-(s + w["router_bias"]), axis=-1, stable=True)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order[:, :sz["top_k"]]].set(True)
    weight = (sz["scale"] * s / jnp.sum(jnp.where(chosen, s, 0.0), axis=-1,
                                        keepdims=True)).astype(b.dtype)
    held = jnp.asarray(sz["held"])
    mine = jnp.where(chosen[:, held], weight[:, held], 0.0).T  # [held, n]

    def one_expert(out, e):
        wg, wu, wd, mine_e = e
        return out + mine_e[:, None] * swiglu(b, wg, wu, wd, mm), None

    out, _ = jax.lax.scan(one_expert, swiglu(b, w["sg"], w["su"], w["sd"], mm),
                          (w["wg"], w["wu"], w["wd"], mine))
    return out


@functools.partial(jax.jit, static_argnames=("kind", "sz", "mode"))
def layer(w, h, length, *, kind, sz, mode):
    """One layer on one sequence h [n, H] of ``length`` valid tokens;
    ``sz`` is ``sizes(cfg)`` as a sorted tuple of items (hashable)."""
    mm = reference.matmul(mode)
    sz = dict(sz)
    mixer, ffn = kind
    a = rms(h, w["g1"], sz["eps"])
    h = h + (kda(w["mixer"], a, sz, mm) if mixer == "kda"
             else mla(w["mixer"], a, length, sz, mm))
    b = rms(h, w["g2"], sz["eps"])
    if ffn == "dense":
        return h + swiglu(b, w["ffn"]["wg"], w["ffn"]["wu"], w["ffn"]["wd"],
                          mm)
    return h + routed(w["ffn"], b, sz, mm)


@functools.partial(jax.jit, static_argnames=("mode",))
def head_block(head, hb, targets, negatives, *, mode):
    z = reference.matmul(mode)(hb, head)                   # [m, V]
    lse = jax.nn.logsumexp(z, axis=-1)
    zy = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    zn = jnp.take_along_axis(z, negatives[:, None], axis=1)[:, 0]
    return lse - zy, zy - lse, zn - lse


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "mode"))
def top(gf, head, h, targets, negatives, length, *, eps, vocab, mode):
    """The final norm, the head in token blocks and the loss's sum over
    one sequence's target positions; aux: the AUC's scores."""
    n = h.shape[0]
    h = rms(h, gf, eps)
    block = jax.checkpoint(functools.partial(head_block, mode=mode))
    parts = [block(head, h[lo:lo + HEAD_BLOCK], targets[lo:lo + HEAD_BLOCK],
                   negatives[lo:lo + HEAD_BLOCK])
             for lo in range(0, n, HEAD_BLOCK)]
    ce, lp_pos, lp_neg = (jnp.concatenate(p) for p in zip(*parts))
    has_target = jnp.arange(n) < length - 1
    ln_v = math.log(vocab)
    aux = {"pos": jax.nn.sigmoid(lp_pos + ln_v),
           "neg": jax.nn.sigmoid(lp_neg + ln_v), "has_target": has_target,
           "ce": ce}
    return jnp.sum(jnp.where(has_target, ce, 0.0)), aux


def sequence_loss_sum(params, x, tokens, length, place, first_key, sz: dict,
                      mode: str, grads=None, add=None):
    """Sum over one sequence's target positions of the next token's
    cross-entropy, and aux.  With ``grads`` (a dict, empty at first) also
    the backward, layer by layer from the kept layer inputs: a layer's
    parameter gradient is added into ``grads`` as it is made (``add(total,
    g)``), so that no whole gradient tree of one sequence stands beside
    the batch's; returns the gradient to x as a third value."""
    n = x.shape[0]
    length = int(length)
    targets = np.zeros(n, np.int32)
    targets[:n - 1] = tokens[1:]
    negatives = negatives_of(sz["neg_seed"], place, first_key, length, n,
                             sz["vocab"])
    frozen = tuple(sorted(sz.items()))
    assert len(params["layers"]) == len(sz["layers"])
    layers = [functools.partial(layer, kind=kind, sz=frozen, mode=mode)
              for kind in sz["layers"]]
    hs = [x]
    for f, w in zip(layers, params["layers"]):
        hs.append(f(w, hs[-1], length))
    tail = functools.partial(top, eps=sz["eps"], vocab=sz["vocab"], mode=mode)
    args = (jnp.asarray(targets), jnp.asarray(negatives), length)
    if grads is None:
        return tail(params["gf"], params["head"], hs[-1], *args)

    def accumulate(where, key, g):
        where[key] = g if where.get(key) is None else add(where[key], g)

    total, vjp, aux = jax.vjp(lambda gf, head, h: tail(gf, head, h, *args),
                              params["gf"], params["head"], hs.pop(),
                              has_aux=True)
    d_gf, d_head, ct = vjp(jnp.ones_like(total))
    accumulate(grads, "gf", d_gf)
    accumulate(grads, "head", d_head)
    per_layer = grads.setdefault("layers", [{} for _ in layers])
    for l in reversed(range(len(layers))):
        # recomputed inside the backward's own program: what a layer
        # keeps for its backward is then the compiler's to place
        _, vjp = jax.vjp(
            jax.checkpoint(lambda w, h, l=l: layers[l](w, h, length)),
            params["layers"][l], hs.pop())
        d_w, ct = vjp(ct)
        accumulate(per_layer[l], "w", d_w)
        del d_w, vjp
    return total, aux, ct


@functools.partial(jax.jit, donate_argnums=0)
def scaled(tree, scale):
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), tree)


def batch_loss(params, mf_masked, batch, cfg: dict, mode: str,
               with_grads: bool = False, add=None):
    """Mean loss of one feed batch; with ``with_grads`` also the gradient
    of that mean to the parameters and, per occurrence [L, B, D], to the
    pulled rows (``reference/ouro_2p6b.py::batch_loss``'s contract)."""
    add = add or (lambda a, b: jax.tree.map(jnp.add, a, b))
    sz = sizes(cfg)
    idx, lengths = rows_and_lengths(batch)
    keys = np.asarray(batch["seq_keys"])                   # [B, L]
    tokens = np.clip(keys - int(cfg["loss"]["key_base"]), 0,
                     sz["vocab"] - 1)
    count = int(np.maximum(lengths - 1, 0).sum())
    scale = 1.0 / max(count, 1)
    total, aux_all = 0.0, []
    grads = {} if with_grads else None
    g_rows = np.zeros(idx.shape + (mf_masked.shape[1],), np.float32) \
        if with_grads else None
    for b in range(idx.shape[1]):
        if lengths[b] <= 0:
            continue
        pos = jnp.arange(idx.shape[0]) < lengths[b]
        x = jnp.where(pos[:, None], mf_masked[jnp.asarray(idx[:, b])], 0.0)
        out = sequence_loss_sum(params, x, tokens[b], lengths[b], b,
                                int(keys[b, 0]), sz, mode, grads, add)
        if with_grads:
            g_rows[:, b] = np.asarray(out[2].astype(jnp.float32)) * scale
        total += float(out[0])
        aux_all.append(out[1])
    out = {"loss": total * scale, "targets": count, "aux": aux_all}
    if with_grads:
        grads["layers"] = [g["w"] for g in grads["layers"]]
        out["d_params"] = scaled(grads, np.float32(scale))
        out["d_rows"] = g_rows
    return out


def step(rows, params, m, v, t, batch, cfg, mode="float32"):
    """The whole plain step from a given state: returns the new rows,
    parameters and moments, the loss and the AUC pairs."""
    out = batch_loss(params, created_mf(rows), batch, cfg, mode,
                     with_grads=True)
    rows = push_rows(rows, batch, out["d_rows"], cfg["table"]["sgd"])
    params, m, v = reference._adam(params, m, v, out["d_params"],
                                   np.float32(t))
    return rows, params, m, v, out
