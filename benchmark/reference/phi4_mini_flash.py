"""The plain reference of the decoder-hybrid-decoder step, independent of
``paddlebox_tpu/models/sambay.py`` and of the program's tied-head push.

SambaY with differential attention ("Decoder-Hybrid-Decoder Architecture
for Efficient Reasoning with Long Generation", arXiv:2507.06607;
config.json of microsoft/Phi-4-mini-flash-reasoning) as the configuration
file states it and cuts it.  One sequence at a time; float32
``jax.numpy``; every projection through ``mm`` (``reference/step.py``:
operands rounded to bfloat16 where the program runs on a TPU, plain
float32 elsewhere); the selective scan **token by token**, each attention
as a full masked softmax one differential head at a time with its two
maps subtracted, the head ``LNf(h) E^T`` from the rows it is given.  No
chunks, no query blocks, no sliced windows.

    LN(z; g, b) = g * (z - mean z) / sqrt(var z + eps) + b   (an all-zero
                  z passes no gradient, as ``ouro.rms``)
    layer l on h [n, H]:  a = LN1(h);  h' = h + mixer_l(a)
                          h'' = h' + (silu(LN2(h') Wg) * (LN2(h') Wu)) Wd
    Mamba:  [x ; z] = a W_in;  x <- silu(conv(x) + b_c)
            [dr ; B_t ; C_t] = x W_x;  dt = softplus(dr W_dt + b_dt)
            s_t = exp(dt_t * A) . s_{t-1} + (dt_t * x_t) (x) B_t
            y_t = s_t C_t + D . x_t;  mixer = (y * silu(z)) W_out
            the last Mamba layer ahead of a GMU hands on m = y
    GMU:    mixer = (m * silu(a W_1)) W_2
    attention (window | full | cross):
            [q ; k ; v] = a Wqkv + b (cross: q only; k, v the full layer's)
            head j of heads / 2: (q1, q2) = q[2j], q[2j + 1];  its kv group
            j // 2: (k1, k2), v [2d]
            A_i = softmax(q_i k_i^T / sqrt(d) + mask)   mask: key <= query,
                  key < length, under the window also key > query - window
            lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
            o_j = RMS((A_1 - lambda A_2) v; g_sub) * (1 - lambda_init(l))
            mixer = concat_j(o_j) W_o + b_o
    out = LNf(h_L) E^T;  loss = mean over target positions of
          -log softmax(out_i)[token_{i+1}]

E [V, H] is the created-masked ``mf`` of the batch's ``head_rows`` (the
working-set rows of the held vocabulary ids), read once; its gradient is
merged with the occurrences' before the sparse rule as the program
merges it (``push_rows``): a row's merged gradient is the sum over its
occurrences plus the head's, negated (the rule adds what it is handed,
and this model's rows descend), show and click count occurrences only, and
the rule moves only rows that an occurrence touched, so the head's
gradient to a row the batch does not contain is dropped.

A sequence's backward goes layer by layer from the kept layer inputs; the
memory's and the shared KV's cotangents are summed over their readers on
the way back and handed to the layer that gave them.  The scan keeps a
state a ``SCAN_BLOCK`` tokens.  Both are management of memory, not of
the arithmetic.

The parameter tree is the program's (``layers`` a list of one dict a
layer, ``lnf_g``, ``lnf_b``; no head).  The interface is the one
``generators/seq_epochs.py::LoopReferenceCheck`` calls.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.flops_sambay import layer_kinds
from benchmark.reference import step as reference
from benchmark.reference.kimi_linear_48b import (adam_unstacked,    # noqa
                                                 conv, host_leaves,
                                                 leaf_sq_dist, named_leaves,
                                                 scaled, unstack)
from benchmark.reference.ouro_2p6b import (created_mf, negatives_of,  # noqa
                                           rms, rows_and_lengths, sq_dist)

HEAD_BLOCK = 1024
SCAN_BLOCK = 64
MLP_BLOCK = 2048


def sizes(cfg: dict) -> dict:
    """What the equations need of a configuration file; the layers held
    here and their kinds as ``harness/flops_sambay.py`` reads them off
    the published indices."""
    heads = int(cfg["num_attention_heads"])
    return {
        "layers": layer_kinds(cfg),
        "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["hidden_size"]) // heads,
        "window": int(cfg["sliding_window"]),
        "d_inner": int(cfg["mamba"]["expand"]) * int(cfg["hidden_size"]),
        "d_state": int(cfg["mamba"]["d_state"]),
        "dt_rank": int(cfg["mamba"]["dt_rank"]),
        "vocab": int(cfg["vocab_size"]),
        "eps": float(cfg["layer_norm_eps"]),
        "neg_seed": int(cfg["loss"]["negative_seed"])}


# -- the layers, one sequence -----------------------------------------------

def ln(z, g, b, eps):
    zero = jnp.all(z == 0, axis=-1, keepdims=True)
    z = jnp.where(zero, jax.lax.stop_gradient(z), z)
    c = z - jnp.mean(z, axis=-1, keepdims=True)
    return g * c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) + b


def scan_tokens(x, dt, a, bm, cm):
    """The recurrence, token by token: x, dt [n, D], a [N, D], bm, cm
    [n, N] -> y [n, D] with y_t = s_t C_t."""
    n, d = x.shape
    pad = -n % SCAN_BLOCK
    if pad:     # tokens past the end leave the state as it is
        x, dt, bm, cm = (jnp.concatenate(
            [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)])
            for t in (x, dt, bm, cm))

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t[None, :] * a) * state \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return state, jnp.sum(state * c_t[:, None], axis=0)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(t.reshape((-1, SCAN_BLOCK) + t.shape[1:])
               for t in (x, dt, bm, cm))
    _, y = jax.lax.scan(block, jnp.zeros((a.shape[0], d), x.dtype), xs)
    return y.reshape(-1, d)[:n]


def mamba(w, a, sz, mm):
    di, ns, r = sz["d_inner"], sz["d_state"], sz["dt_rank"]
    xz = mm(a, w["w_in"])
    x = jax.nn.silu(conv(xz[:, :di], w["conv"]) + w["b_conv"])
    proj = mm(x, w["w_x"])
    dt = jax.nn.softplus(mm(proj[:, :r], w["w_dt"]) + w["b_dt"])
    y = scan_tokens(x, dt, -jnp.exp(w["a_log"]).T, proj[:, r:r + ns],
                    proj[:, r + ns:]) + w["d"] * x
    return mm(y * jax.nn.silu(xz[:, di:]), w["w_out"]), y


def attention(kind, layer_id, w, a, length, kv, sz, mm):
    n = a.shape[0]
    nh, nkv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    qkv = mm(a, w["wqkv"]) + w["bqkv"]
    q = qkv[:, :nh * d].reshape(n, nh // 2, 2, d)
    if kind == "attn_cross":
        k, v = kv
    else:
        k = qkv[:, nh * d:(nh + nkv) * d].reshape(n, nkv // 2, 2, d)
        v = qkv[:, (nh + nkv) * d:].reshape(n, nkv // 2, 2 * d)
    init = 0.8 - 0.6 * math.exp(-0.3 * layer_id)
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
        - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + init
    i = jnp.arange(n)
    allowed = (i[None, :] <= i[:, None]) & (i[None, :] < length)
    if kind == "swa":
        allowed = allowed & (i[None, :] > i[:, None] - sz["window"])
    per_group = nh // nkv

    @jax.checkpoint
    def head(args):
        q_j, j = args                                   # [n, 2, d]
        k_j, v_j = k[:, j // per_group], v[:, j // per_group]

        def probs(side):
            s = mm(q_j[:, side], k_j[:, side].T) / math.sqrt(d)
            return jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)

        return mm(probs(0) - lam * probs(1), v_j)       # [n, 2d]

    o = jax.lax.map(head, (jnp.transpose(q, (1, 0, 2, 3)),
                           jnp.arange(nh // 2)))
    o = rms(jnp.transpose(o, (1, 0, 2)), w["g_sub"], sz["eps"]) \
        * (1.0 - init)
    return mm(o.reshape(n, nh * d), w["wo"]) + w["bo"], (k, v)


@functools.partial(jax.jit, static_argnames=("spec", "sz", "mode"))
def layer(w, h, length, shared, *, spec, sz, mode):
    """One layer on one sequence h [n, H] of ``length`` valid tokens;
    ``shared`` is what it reads of an earlier layer (the memory, the KV)
    or None; returns (h, what it would hand on).  ``spec`` = (published
    index, kind), ``sz`` is ``sizes(cfg)`` as a sorted tuple of items."""
    mm = reference.matmul(mode)
    sz = dict(sz)
    layer_id, kind = spec

    # a checkpoint a half layer and a feed-forward block: the backward
    # of a layer then keeps its inputs, not every projection of 8,192
    # tokens (management of memory, as the docstring says)
    @jax.checkpoint
    def mixer(w, h, shared):
        a = ln(h, w["ln1_g"], w["ln1_b"], sz["eps"])
        if kind == "mamba":
            return mamba(w["mixer"], a, sz, mm)
        if kind == "gmu":
            return mm(shared * jax.nn.silu(mm(a, w["mixer"]["w1"])),
                      w["mixer"]["w2"]), None
        return attention(kind, layer_id, w["mixer"], a, length, shared, sz,
                         mm)

    @jax.checkpoint
    def mlp(hb):
        b = ln(hb, w["ln2_g"], w["ln2_b"], sz["eps"])
        f = w["mlp"]
        return hb + mm(jax.nn.silu(mm(b, f["wg"])) * mm(b, f["wu"]), f["wd"])

    out, handed = mixer(w, h, shared)
    h = h + out
    n = h.shape[0]
    if n % MLP_BLOCK:
        return mlp(h), handed
    return jax.lax.map(mlp, h.reshape(-1, MLP_BLOCK, h.shape[1])
                       ).reshape(h.shape), handed


@functools.partial(jax.jit, static_argnames=("mode",))
def head_block(e, hb, targets, negatives, *, mode):
    z = reference.matmul(mode)(hb, e.T)                    # [m, V]
    lse = jax.nn.logsumexp(z, axis=-1)
    zy = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    zn = jnp.take_along_axis(z, negatives[:, None], axis=1)[:, 0]
    return lse - zy, zy - lse, zn - lse


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "mode"))
def top(g, b, e, h, targets, negatives, length, *, eps, vocab, mode):
    """The final norm, the tied head in token blocks and the loss's sum
    over one sequence's target positions; aux: the AUC's scores."""
    n = h.shape[0]
    h = ln(h, g, b, eps)
    block = jax.checkpoint(functools.partial(head_block, mode=mode))
    parts = [block(e, h[lo:lo + HEAD_BLOCK], targets[lo:lo + HEAD_BLOCK],
                   negatives[lo:lo + HEAD_BLOCK])
             for lo in range(0, n, HEAD_BLOCK)]
    ce, lp_pos, lp_neg = (jnp.concatenate(p) for p in zip(*parts))
    has_target = jnp.arange(n) < length - 1
    ln_v = math.log(vocab)
    aux = {"pos": jax.nn.sigmoid(lp_pos + ln_v),
           "neg": jax.nn.sigmoid(lp_neg + ln_v), "has_target": has_target,
           "ce": ce}
    return jnp.sum(jnp.where(has_target, ce, 0.0)), aux


def readers(sz: dict) -> dict:
    """Layer -> the layer whose memory or KV it reads; the givers are the
    last Mamba layer ahead of the first GMU and the last full-attention
    layer ahead of the first cross layer."""
    kinds = [k for _, k in sz["layers"]]
    out = {}
    for reader, giver in (("gmu", "mamba"), ("attn_cross", "attn_full")):
        if reader in kinds:
            source = max(i for i, k in enumerate(
                kinds[:kinds.index(reader)]) if k == giver)
            out.update({i: source for i, k in enumerate(kinds)
                        if k == reader})
    return out


def sequence_loss_sum(params, x, e, tokens, length, place, first_key,
                      sz: dict, mode: str, grads=None, add=None):
    """Sum over one sequence's target positions of the next token's
    cross-entropy, and aux.  With ``grads`` (a dict, empty at first) also
    the backward, layer by layer from the kept layer inputs, a layer's
    parameter gradient added into ``grads`` as it is made; returns the
    gradients to x and to e as a third and a fourth value."""
    n = x.shape[0]
    length = int(length)
    targets = np.zeros(n, np.int32)
    targets[:n - 1] = tokens[1:]
    negatives = negatives_of(sz["neg_seed"], place, first_key, length, n,
                             sz["vocab"])
    frozen = tuple(sorted(sz.items()))
    assert len(params["layers"]) == len(sz["layers"])
    layers = [functools.partial(layer, spec=spec, sz=frozen, mode=mode)
              for spec in sz["layers"]]
    reads = readers(sz)
    hs, handed = [x], []
    for i, (f, w) in enumerate(zip(layers, params["layers"])):
        shared = handed[reads[i]] if i in reads else None
        h, out = f(w, hs[-1], length, shared)
        hs.append(h)
        handed.append(out if i in reads.values() else None)
    tail = functools.partial(top, eps=sz["eps"], vocab=sz["vocab"], mode=mode)
    args = (jnp.asarray(targets), jnp.asarray(negatives), length)
    if grads is None:
        return tail(params["lnf_g"], params["lnf_b"], e, hs[-1], *args)

    def accumulate(where, key, g):
        where[key] = g if where.get(key) is None else add(where[key], g)

    total, vjp, aux = jax.vjp(lambda g, b, e, h: tail(g, b, e, h, *args),
                              params["lnf_g"], params["lnf_b"], e, hs.pop(),
                              has_aux=True)
    d_g, d_b, d_e, ct = vjp(jnp.ones_like(total))
    accumulate(grads, "lnf_g", d_g)
    accumulate(grads, "lnf_b", d_b)
    per_layer = grads.setdefault("layers", [{} for _ in layers])
    ct_handed = {}          # giver -> the cotangent its readers have summed
    for i in reversed(range(len(layers))):
        shared = handed[reads[i]] if i in reads else None
        gives = i in reads.values()

        def run(w, h, s, i=i, gives=gives):
            h, out = layers[i](w, h, length, s)
            return h, (out if gives else None)

        # recomputed inside the backward's own program
        _, vjp = jax.vjp(jax.checkpoint(run), params["layers"][i], hs.pop(),
                         shared)
        zero = jax.tree.map(jnp.zeros_like, handed[i]) if gives else None
        d_w, ct, d_shared = vjp((ct, ct_handed.pop(i, zero)))
        if i in reads:
            accumulate(ct_handed, reads[i], d_shared)
        accumulate(per_layer[i], "w", d_w)
        del d_w, vjp
    return total, aux, ct, d_e


def batch_loss(params, mf_masked, batch, cfg: dict, mode: str,
               with_grads: bool = False, add=None):
    """Mean loss of one feed batch; with ``with_grads`` also the gradient
    of that mean to the parameters and to the rows: ``d_rows`` =
    {``occ``: per occurrence [L, B, D], ``head``: per head row [V, D]}.
    ``batch`` carries ``head_rows`` [V] beside ``seq_epochs``'s planes."""
    add = add or (lambda a, b: jax.tree.map(jnp.add, a, b))
    sz = sizes(cfg)
    idx, lengths = rows_and_lengths(batch)
    keys = np.asarray(batch["seq_keys"])                   # [B, L]
    tokens = np.clip(keys - int(cfg["loss"]["key_base"]), 0,
                     sz["vocab"] - 1)
    e = mf_masked[jnp.asarray(np.asarray(batch["head_rows"]))]
    count = int(np.maximum(lengths - 1, 0).sum())
    scale = 1.0 / max(count, 1)
    total, aux_all = 0.0, []
    grads = {} if with_grads else None
    d_head = {}
    g_rows = np.zeros(idx.shape + (mf_masked.shape[1],), np.float32) \
        if with_grads else None
    for b in range(idx.shape[1]):
        if lengths[b] <= 0:
            continue
        pos = jnp.arange(idx.shape[0]) < lengths[b]
        x = jnp.where(pos[:, None], mf_masked[jnp.asarray(idx[:, b])], 0.0)
        out = sequence_loss_sum(params, x, e, tokens[b], lengths[b], b,
                                int(keys[b, 0]), sz, mode, grads, add)
        if with_grads:
            g_rows[:, b] = np.asarray(out[2].astype(jnp.float32)) * scale
            d_head["e"] = out[3] if "e" not in d_head \
                else add(d_head["e"], out[3])
        total += float(out[0])
        aux_all.append(out[1])
    out = {"loss": total * scale, "targets": count, "aux": aux_all}
    if with_grads:
        grads["layers"] = [g["w"] for g in grads["layers"]]
        out["d_params"] = scaled(grads, np.float32(scale))
        out["d_rows"] = {
            "occ": g_rows,
            "head": np.asarray(d_head["e"].astype(jnp.float32)) * scale}
    return out


def push_rows(rows, batch, d_rows: dict, sgd: dict):
    """``reference/step.py``'s row rule on the batch's merged gradient,
    **negated**: the rule adds what it is handed and this model's rows
    descend (``assumed``, "table.sgd").  Every position is an example of
    its own with one key (g_show 1, g_click its example's label,
    ``embed_w``'s gradient zero), and the head's gradient to a row is
    added to that row's first occurrence, so that the
    rule's per-row sum is occurrences + head; a head row that no position
    of the batch holds has no occurrence to ride on and is dropped, as
    the rule would leave it (g_show 0)."""
    idx, lengths = rows_and_lengths(batch)
    l, b = idx.shape
    mask = (np.arange(l)[:, None] < lengths[None, :]).reshape(1, 1, l * b)
    flat = np.where(mask, idx.reshape(1, 1, l * b), 0)
    labels = np.tile(np.asarray(batch["labels"], np.float32), l)
    d = np.zeros((l * b, 1, 3 + d_rows["occ"].shape[-1]), np.float32)
    d[:, 0, 3:] = -d_rows["occ"].reshape(l * b, -1)
    n = np.asarray(rows["show"]).shape[0]
    head_rows = np.asarray(batch["head_rows"])
    place = np.full(n, -1, np.int64)
    place[head_rows] = np.arange(len(head_rows))
    held, first = np.unique(flat[0, 0], return_index=True)
    ok = (held > 0) & (place[held] >= 0)
    d[first[ok], 0, 3:] -= d_rows["head"][place[held[ok]]]
    return reference._push_adagrad(
        {f: jnp.asarray(rows[f]) for f in reference.ROW_FIELDS},
        jnp.asarray(flat), jnp.asarray(mask), jnp.asarray(labels),
        jnp.asarray(d), sgd)


def step(rows, params, m, v, t, batch, cfg, mode="float32"):
    """The whole plain step from a given state: returns the new rows,
    parameters and moments, the loss and the AUC pairs."""
    out = batch_loss(params, created_mf(rows), batch, cfg, mode,
                     with_grads=True)
    rows = push_rows(rows, batch, out["d_rows"], cfg["table"]["sgd"])
    params, m, v = reference._adam(params, m, v, out["d_params"],
                                   np.float32(t))
    return rows, params, m, v, out
