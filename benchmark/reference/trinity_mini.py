"""The plain reference of the AFMoE step, independent of
``paddlebox_tpu/models/afmoe.py``, ``hybridlm.py``, ``sambay.py`` and
``parallel/moe.py``.

Trinity-Mini (config.json of arcee-ai/Trinity-Mini, ``model_type``
``afmoe``; the modelling code's attention, decoder layer, router and the
bias rule, as the configuration file's ``assumed`` says) as the
configuration file states it and cuts it.  One sequence at a time;
float32 ``jax.numpy``; every projection and attention product through
``mm`` (``reference/step.py``: operands rounded to bfloat16 where the
program runs on a TPU, plain float32 elsewhere); attention as a full
softmax over the mask, one head and a block of its queries at a time;
the experts as a loop over the held ones with a mask.  No window
slicing, no sorting, no grouped product.  Blocks and checkpoints manage
memory, not the arithmetic (a routed layer's backward needed 5.6 GB of
temporaries without them, more than the chip had beside the program).

    RMS(z; g) = g * z / sqrt(mean(z^2) + eps)   (an all-zero z passes no
                                                  gradient: ``ouro.rms``)
    x = row * sqrt(hidden)                      (mup_enabled)
    layer l on h [n, H]:
      a = RMS(h; g1)
      q = RMS_head(a Wq; g_q), k = RMS_head(a Wk; g_k), v = a Wv
      sliding layer: q, k rotated (rotate-half, theta, position = index)
      o_h = softmax(q_h k_{h // group}^T / sqrt(d) + M) v_{h // group}
            M: j <= t, j < length, sliding: t - j < window
      h' = h + RMS((o * sigmoid(a Wgate)) Wo; g1_post)
      b = RMS(h'; g2)
      dense: f = (silu(b Wg) * (b Wu)) Wd
      routed: s = sigmoid(b Wr) over all ``published.num_experts``
        (float32); chosen = the top-k of s + bias;
        w_e = scale * s_e / sum_chosen s;
        f = E_shared(b) + sum over e chosen AND held of w_e E_e(b)
      h'' = h' + RMS(f; g2_post)
    out = RMS(h_L; gf) W_head
    loss = mean over target positions of -log softmax(out_i)[token_{i+1}]
    after the update (``balance_bias``), a routed layer each: c_e the
      positions inside their sequence (b not all zero) that chose e,
      summed over the batch; d_e = rate * sign(mean(c) - c_e);
      bias_e += d_e - mean(d)

The share (``share`` in the configuration file): this chip holds
``num_experts`` experts of each routed layer, ids ``expert_first`` on;
what the other experts would add is left out, here as in the program.
The parameter tree is the program's (``layers`` a list of one dict a
layer: ``g1 g1_post g2 g2_post mixer{...} ffn{...}``, ``gf``, ``head``);
the interface is the one ``generators/seq_epochs.py::LoopReferenceCheck``
calls, and ``batch_loss`` also returns the routed layers' counts
(``route``), which ``balance_bias`` reads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import step as reference
from benchmark.reference.kimi_linear_48b import (adam_unstacked,  # noqa
                                                 host_leaves, leaf_sq_dist,
                                                 named_leaves, scaled,
                                                 swiglu, top, unstack)
from benchmark.reference.ouro_2p6b import (created_mf, negatives_of,  # noqa
                                           push_rows, rms, rotate,
                                           rows_and_lengths, sq_dist)

MIXERS = {"sliding_attention": "swa", "full_attention": "attn_full"}
QUERY_ROWS = 1024     # queries a block of the attention's softmax


def sizes(cfg: dict) -> dict:
    """What the equations need of a configuration file."""
    first = int(cfg["share"]["layer_first"])
    dense = int(cfg["num_dense_layers"])
    held = int(cfg["share"]["expert_first"])
    return {
        "layers": tuple(
            (MIXERS[cfg["layer_types"][l]], "dense" if l < dense else "moe")
            for l in range(first, first + int(cfg["num_hidden_layers"]))),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "window": int(cfg["sliding_window"]),
        "theta": float(cfg["rope_theta"]),
        "experts": int(cfg["published"]["num_experts"]),
        "held": tuple(range(held, held + int(cfg["num_experts"]))),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["route_scale"]),
        "input_scale": math.sqrt(int(cfg["hidden_size"]))
        if cfg["mup_enabled"] else 1.0,
        "vocab": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "neg_seed": int(cfg["loss"]["negative_seed"])}


# -- the layers, one sequence -----------------------------------------------

def attention(w, a, length, window, sz, mm):
    """The attention mixer on a [n, H]: a full softmax over the mask, one
    head at a time and its queries ``QUERY_ROWS`` at a time (a row's
    softmax is its own), each under a checkpoint, so that one [rows, n]
    block of scores is alive at a time."""
    n = a.shape[0]
    nh, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = rms(mm(a, w["wq"]).reshape(n, nh, d), w["g_q"], sz["eps"])
    k = rms(mm(a, w["wk"]).reshape(n, g, d), w["g_k"], sz["eps"])
    v = mm(a, w["wv"]).reshape(n, g, d)
    if window:
        q, k = rotate(q, sz["theta"]), rotate(k, sz["theta"])
    rows = math.gcd(n, QUERY_ROWS)
    j = jnp.arange(n)
    group = jnp.arange(nh) // (nh // g)              # a head's kv head

    def head(args):
        q_h, k_h, v_h = args                         # [n, d] each

        @jax.checkpoint
        def block(args):
            q_b, lo = args                           # [rows, d]
            t = lo + jnp.arange(rows)
            allowed = (j[None, :] <= t[:, None]) & (j[None, :] < length)
            if window:
                allowed = allowed & (t[:, None] - j[None, :] < window)
            scores = mm(q_b, k_h.T) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30),
                                   axis=-1)
            return mm(probs, v_h)

        return jax.lax.map(block, (q_h.reshape(n // rows, rows, d),
                                   jnp.arange(0, n, rows))).reshape(n, d)

    o = jax.lax.map(head, (jnp.transpose(q, (1, 0, 2)), k[:, group].transpose(
        1, 0, 2), v[:, group].transpose(1, 0, 2)))
    o = jnp.transpose(o, (1, 0, 2)).reshape(n, nh * d)
    return mm(o * jax.nn.sigmoid(mm(a, w["wgate"])), w["wo"])


def routed(w, b, length, sz, mm):
    """The held experts' part, expert by expert over every token, with a
    mask; then the shared expert.  Also the positions inside the
    sequence (b not all zero) that chose each expert of all of them."""
    s = jax.nn.sigmoid(jnp.matmul(b.astype(jnp.float32),
                                  w["router"].astype(jnp.float32)))
    order = jnp.argsort(-(s + w["router_bias"]), axis=-1, stable=True)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order[:, :sz["top_k"]]].set(True)
    weight = (sz["scale"] * s / jnp.sum(jnp.where(chosen, s, 0.0), axis=-1,
                                        keepdims=True)).astype(b.dtype)
    live = (jnp.arange(b.shape[0]) < length) & jnp.any(b != 0, axis=-1)
    route = jnp.sum(chosen & live[:, None], axis=0).astype(jnp.float32)
    held = jnp.asarray(sz["held"])
    mine = jnp.where(chosen[:, held], weight[:, held], 0.0).T  # [held, n]

    @jax.checkpoint       # an expert's intermediates alive one at a time
    def expert(wg, wu, wd, mine_e):
        return mine_e[:, None] * swiglu(b, wg, wu, wd, mm)

    def one_expert(out, e):
        return out + expert(*e), None

    out, _ = jax.lax.scan(one_expert, swiglu(b, w["sg"], w["su"], w["sd"], mm),
                          (w["wg"], w["wu"], w["wd"], mine))
    return out, route


@functools.partial(jax.jit, static_argnames=("kind", "sz", "mode"))
def layer(w, h, length, *, kind, sz, mode):
    """One layer on one sequence h [n, H] of ``length`` valid tokens ->
    (h'', the routed layer's counts over all experts, zeros for a dense
    one); ``sz`` is ``sizes(cfg)`` as a sorted tuple of items
    (hashable)."""
    mm = reference.matmul(mode)
    sz = dict(sz)
    mixer, ffn = kind
    a = rms(h, w["g1"], sz["eps"])
    o = attention(w["mixer"], a, length,
                  sz["window"] if mixer == "swa" else 0, sz, mm)
    h = h + rms(o, w["g1_post"], sz["eps"])
    b = rms(h, w["g2"], sz["eps"])
    if ffn == "dense":
        f = swiglu(b, w["ffn"]["wg"], w["ffn"]["wu"], w["ffn"]["wd"], mm)
        route = jnp.zeros((sz["experts"],), jnp.float32)
    else:
        f, route = routed(w["ffn"], b, length, sz, mm)
    return h + rms(f, w["g2_post"], sz["eps"]), route


def sequence_loss_sum(params, x, tokens, length, place, first_key, sz: dict,
                      mode: str, grads=None, add=None):
    """Sum over one sequence's target positions of the next token's
    cross-entropy, aux, and the routed layers' counts [routed layers,
    experts].  With ``grads`` (a dict, empty at first) also the backward,
    layer by layer from the kept layer inputs, each layer's parameter
    gradient added into ``grads`` as it is made; then the gradient to x
    comes fourth."""
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
    hs, routes = [x * sz["input_scale"]], []
    for (_, ffn), f, w in zip(sz["layers"], layers, params["layers"]):
        h, route = f(w, hs[-1], length)
        hs.append(h)
        if ffn == "moe":
            routes.append(route)
    route = jnp.stack(routes) if routes else None
    tail = functools.partial(top, eps=sz["eps"], vocab=sz["vocab"], mode=mode)
    args = (jnp.asarray(targets), jnp.asarray(negatives), length)
    if grads is None:
        return tail(params["gf"], params["head"], hs[-1], *args) + (route,)

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
        _, vjp, _ = jax.vjp(
            jax.checkpoint(lambda w, h, l=l: layers[l](w, h, length)),
            params["layers"][l], hs.pop(), has_aux=True)
        d_w, ct = vjp(ct)
        accumulate(per_layer[l], "w", d_w)
        del d_w, vjp
    # the muP scale's own factor on the way back to the row
    return total, aux, route, ct * sz["input_scale"]


def batch_loss(params, mf_masked, batch, cfg: dict, mode: str,
               with_grads: bool = False, add=None):
    """Mean loss of one feed batch; with ``with_grads`` also the gradient
    of that mean to the parameters and, per occurrence [L, B, D], to the
    pulled rows (``reference/ouro_2p6b.py::batch_loss``'s contract); and
    ``route``: the routed layers' counts summed over the batch."""
    add = add or (lambda a, b: jax.tree.map(jnp.add, a, b))
    sz = sizes(cfg)
    idx, lengths = rows_and_lengths(batch)
    keys = np.asarray(batch["seq_keys"])                   # [B, L]
    tokens = np.clip(keys - int(cfg["loss"]["key_base"]), 0,
                     sz["vocab"] - 1)
    count = int(np.maximum(lengths - 1, 0).sum())
    scale = 1.0 / max(count, 1)
    total, aux_all, route = 0.0, [], 0.0
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
            g_rows[:, b] = np.asarray(out[3].astype(jnp.float32)) * scale
        total += float(out[0])
        aux_all.append(out[1])
        route = route + np.asarray(out[2])
    out = {"loss": total * scale, "targets": count, "aux": aux_all,
           "route": route}
    if with_grads:
        grads["layers"] = [g["w"] for g in grads["layers"]]
        out["d_params"] = scaled(grads, np.float32(scale))
        out["d_rows"] = g_rows
    return out


def balance_bias(params: dict, route, cfg: dict) -> dict:
    """The routing bias's update after the step (module docstring), from
    the routed layers' counts [routed layers, experts]."""
    rate = float(cfg["load_balance_coeff"])
    layers, i = list(params["layers"]), 0
    for l, (_, ffn) in enumerate(sizes(cfg)["layers"]):
        if ffn != "moe":
            continue
        c = jnp.asarray(route[i], jnp.float32)
        i += 1
        d = rate * jnp.sign(jnp.mean(c) - c)
        f = layers[l]["ffn"]
        bias = f["router_bias"]
        layers[l] = {**layers[l], "ffn": {
            **f, "router_bias": (bias + (d - jnp.mean(d))).astype(
                bias.dtype)}}
    return {**params, "layers": layers}


def step(rows, params, m, v, t, batch, cfg, mode="float32"):
    """The whole plain step from a given state: returns the new rows,
    parameters and moments, the loss and the AUC pairs."""
    out = batch_loss(params, created_mf(rows), batch, cfg, mode,
                     with_grads=True)
    rows = push_rows(rows, batch, out["d_rows"], cfg["table"]["sgd"])
    params, m, v = reference._adam(params, m, v, out["d_params"],
                                   np.float32(t))
    params = balance_bias(params, out["route"], cfg)
    return rows, params, m, v, out
