"""Mixture-of-Experts with expert parallelism.

≙ python/paddle/incubate/distributed/models/moe/: MoELayer (moe_layer.py:244)
with MoEScatter/MoEGather over global_scatter/global_gather all2all ops
(:88-151), and the gate zoo (models/moe/gate/): naive, switch (top-1),
gshard (top-2 + aux load-balance loss).

TPU-first formulation: the einsum dispatch/combine form — tokens one-hot
into [E, C] capacity buckets, ``lax.all_to_all`` over the ``ep`` axis moves
expert shards (exactly the reference's global_scatter), experts run batched
matmuls on [E_local, n*C, d] (MXU-friendly), then the inverse path.  No
sorting, no dynamic shapes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.utils import trace


# -- gates (≙ models/moe/gate/{naive,switch,gshard}_gate.py) ---------------

def top1_gate(logits: jnp.ndarray, capacity: int):
    """Switch-style top-1 routing → (dispatch [T,E,C], combine [T,E,C],
    aux_loss).  T = local tokens, E = global experts."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, -1)
    expert = jnp.argmax(probs, -1)                       # [T]
    onehot = jax.nn.one_hot(expert, E, dtype=probs.dtype)
    # position of each token within its expert's capacity bucket
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0      # [T,E]
    keep = (pos >= 0) & (pos < capacity)
    pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    dispatch = (jax.nn.one_hot(pos_c, capacity, dtype=probs.dtype)
                * keep[..., None] * onehot[..., None])   # [T,E,C]
    gate_val = jnp.sum(probs * onehot, -1)               # [T]
    combine = dispatch * gate_val[:, None, None]
    # switch aux loss: E * sum(fraction_tokens * fraction_probs)
    me = jnp.mean(onehot, axis=0)
    ce = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(me * ce)
    return dispatch, combine, aux


def top2_gate(logits: jnp.ndarray, capacity: int):
    """GShard top-2 gate (second expert weighted, shared capacity)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, -1)
    e1 = jnp.argmax(probs, -1)
    oh1 = jax.nn.one_hot(e1, E, dtype=probs.dtype)
    probs2 = probs * (1 - oh1)
    e2 = jnp.argmax(probs2, -1)
    oh2 = jax.nn.one_hot(e2, E, dtype=probs.dtype)
    g1 = jnp.sum(probs * oh1, -1)
    g2 = jnp.sum(probs * oh2, -1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    pos1 = jnp.cumsum(oh1, 0) * oh1 - 1.0
    # second choices queue behind all first choices of the same expert
    pos2 = (jnp.cumsum(oh2, 0) + jnp.sum(oh1, 0, keepdims=True)) * oh2 - 1.0

    def build(oh, pos, gate_val):
        keep = (pos >= 0) & (pos < capacity)
        pc = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        d = (jax.nn.one_hot(pc, capacity, dtype=probs.dtype)
             * keep[..., None] * oh[..., None])
        return d, d * gate_val[:, None, None]

    d1, c1 = build(oh1, pos1, g1)
    d2, c2 = build(oh2, pos2, g2)
    me = jnp.mean(oh1, 0)
    ce = jnp.mean(probs, 0)
    aux = E * jnp.sum(me * ce)
    return d1 + d2, c1 + c2, aux


GATES = {"switch": top1_gate, "gshard": top2_gate, "naive": top1_gate}


# -- expert-parallel layer --------------------------------------------------

@dataclasses.dataclass
class MoEConfig:
    d_model: int
    d_hidden: int
    num_experts: int          # global expert count (divisible by ep size)
    capacity_factor: float = 1.25
    gate: str = "gshard"


class MoELayer:
    """Call apply_sharded inside shard_map with tokens sharded over `ep`.

    params["experts"]: w1 [E, d, h], b1 [E, h], w2 [E, h, d], b2 [E, d] —
    expert dim sharded over ep; params["gate"]: [d, E] replicated.
    """

    def __init__(self, config: MoEConfig, axis: str = "ep"):
        self.cfg = config
        self.axis = axis

    def init(self, key) -> Dict:
        c = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        s1 = (6.0 / (c.d_model + c.d_hidden)) ** 0.5
        return {
            "gate": jax.random.normal(k3, (c.d_model, c.num_experts),
                                      jnp.float32) * 0.02,
            "w1": jax.random.uniform(k1, (c.num_experts, c.d_model,
                                          c.d_hidden), jnp.float32, -s1, s1),
            "b1": jnp.zeros((c.num_experts, c.d_hidden), jnp.float32),
            "w2": jax.random.uniform(k2, (c.num_experts, c.d_hidden,
                                          c.d_model), jnp.float32, -s1, s1),
            "b2": jnp.zeros((c.num_experts, c.d_model), jnp.float32),
        }

    def param_specs(self):
        from jax.sharding import PartitionSpec as P
        ax = self.axis
        return {"gate": P(), "w1": P(ax), "b1": P(ax),
                "w2": P(ax), "b2": P(ax)}

    def capacity(self, tokens_local: int, ep: int) -> int:
        c = self.cfg
        cap = int(self.cfg.capacity_factor * tokens_local * ep
                  / c.num_experts)
        return max(cap, 4)

    def apply_sharded(self, params_local, x, ep: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: [T_local, d].  params_local experts: [E/ep, ...].  Returns
        (y [T_local, d], aux_loss)."""
        c = self.cfg
        T, d = x.shape
        E = c.num_experts
        cap = self.capacity(T, ep)
        logits = x @ params_local["gate"]
        dispatch, combine, aux = GATES[c.gate](logits, cap)
        # local buckets per global expert [E, C, d]
        buckets = jnp.einsum("td,tec->ecd", x, dispatch)
        # ≙ global_scatter: all_to_all so each device holds its experts'
        # buckets from every peer: [E,C,d] → [E/ep, ep*C, d]
        # (global expert id = owner_device * e_loc + local_expert)
        e_loc = E // ep
        buckets = lax.all_to_all(buckets, self.axis, split_axis=0,
                                 concat_axis=1, tiled=True)
        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", buckets,
                                   params_local["w1"])
                        + params_local["b1"][:, None, :])
        out = jnp.einsum("ech,ehd->ecd", h, params_local["w2"]) \
            + params_local["b2"][:, None, :]
        # ≙ global_gather: inverse all_to_all back to source devices
        out = lax.all_to_all(out, self.axis, split_axis=1, concat_axis=0,
                             tiled=True)  # [E, cap, d]
        y = jnp.einsum("ecd,tec->td", out, combine)
        return y, aux

    def apply_dense(self, params, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Unsharded golden path (all experts local) for tests."""
        c = self.cfg
        T, d = x.shape
        cap = self.capacity(T, 1)
        logits = x @ params["gate"]
        dispatch, combine, aux = GATES[c.gate](logits, cap)
        buckets = jnp.einsum("td,tec->ecd", x, dispatch)
        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", buckets, params["w1"])
                        + params["b1"][:, None, :])
        out = jnp.einsum("ech,ehd->ecd", h, params["w2"]) \
            + params["b2"][:, None, :]
        y = jnp.einsum("ecd,tec->td", out, combine)
        return y, aux


# -- a chip's share of a routed layer: sort, keep, one expert a block --------

EXPERT_BLOCK = 1024   # rows of the sorted assignments a block (one expert's)


def route_top_k(x, router, bias, top_k: int, scale: float):
    """Sigmoid routing over ALL experts: s = sigmoid(x W_r) (the one
    product kept in float32: which expert comes eighth must not hang on
    how the device rounds), chosen = top-k of s + bias, weights ``scale *
    s_e / sum over the chosen``.  x [T, H] -> ids [T, k], weights [T, k]."""
    s = jax.nn.sigmoid(jnp.dot(x, router,
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _block(plan, i, size: int, top_k: int):
    """Block i of the sorted held assignments: its expert, the
    assignments in it (ids into [T * k]), their tokens, and which of its
    rows hold one (an expert's last block is filled up with rows that
    hold none).  ``plan`` = (order, load, first_block)."""
    order, load, first_block = plan
    with trace.device_scope("dispatch"):
        e = jnp.sum(first_block <= i) - 1            # the block's expert
        inside = (i - first_block[e]) * size + jnp.arange(size)
        keep = inside < load[e]
        at = jnp.cumsum(load)[e] - load[e] + inside  # place in the sort
        rows = order[jnp.clip(at, 0, order.shape[0] - 1)]
    return e, rows, rows // top_k, keep


def _expert_rows(xs, wt, wg, wu, wd):
    """One expert's SwiGLU on its rows, times their routing weights."""
    with trace.device_scope("experts"):
        return ((jax.nn.silu(xs @ wg) * (xs @ wu)) @ wd) * wt[:, None]


def _block_inputs(x, weights, experts, plan, i, size, top_k):
    e, rows, token, keep = _block(plan, i, size, top_k)
    with trace.device_scope("dispatch"):
        xs = jnp.where(keep[:, None], x[token], 0.0)
        wt = jnp.where(keep, weights[rows], 0.0)
        w_e = tuple(lax.dynamic_index_in_dim(w, e, keepdims=False)
                    for w in experts)
    return e, rows, token, keep, xs, wt, w_e


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _expert_blocks(x, weights, wg, wu, wd, plan, size: int, top_k: int):
    """The held assignments, sorted by expert and cut into blocks of
    ``size`` rows none of which holds two experts, multiplied block by
    block: as many blocks as the data fill (a ``while``, so the backward
    is written out below: the same blocks again, each recomputed and
    differentiated alone, an expert's weight gradient added in place).
    Returns the output [T, H] and the assignments the blocks took."""
    blocks = plan[2][-1]

    def one(i, carry):
        out, taken = carry
        _, _, token, keep, xs, wt, w_e = _block_inputs(
            x, weights, (wg, wu, wd), plan, i, size, top_k)
        ys = _expert_rows(xs, wt, *w_e)
        with trace.device_scope("combine"):
            out = out.at[token].add(jnp.where(keep[:, None], ys, 0.0))
        return out, taken + jnp.sum(keep).astype(jnp.float32)

    return lax.fori_loop(0, blocks, one,
                         (jnp.zeros_like(x), jnp.zeros((), jnp.float32)))


def _expert_blocks_fwd(x, weights, wg, wu, wd, plan, size, top_k):
    return (_expert_blocks(x, weights, wg, wu, wd, plan, size, top_k),
            (x, weights, wg, wu, wd, plan))


def _expert_blocks_bwd(size, top_k, saved, g):
    x, weights, wg, wu, wd, plan = saved

    def one(i, grads):
        dx, dwt, dw = grads
        e, rows, token, keep, xs, wt, w_e = _block_inputs(
            x, weights, (wg, wu, wd), plan, i, size, top_k)
        _, vjp = jax.vjp(_expert_rows, xs, wt, *w_e)
        dxs, dwt_rows, *dw_e = vjp(jnp.where(keep[:, None], g[0][token], 0.0))
        with trace.device_scope("combine"):
            dx = dx.at[token].add(jnp.where(keep[:, None], dxs, 0.0))
            dwt = dwt.at[rows].add(jnp.where(keep, dwt_rows, 0.0))
            dw = tuple(a.at[e].add(b) for a, b in zip(dw, dw_e))
        return dx, dwt, dw

    dx, dwt, dw = lax.fori_loop(
        0, plan[2][-1], one,
        (jnp.zeros_like(x), jnp.zeros_like(weights),
         tuple(jnp.zeros_like(w) for w in (wg, wu, wd))))
    return (dx, dwt, *dw, None)


_expert_blocks.defvjp(_expert_blocks_fwd, _expert_blocks_bwd)


def routed_experts(x, live, router, bias, experts, held, top_k: int,
                   scale: float):
    """The part of a routed SwiGLU layer that the experts held here give:
    sum over e chosen AND in ``held`` of w_e E_e(x), for x [T, H].

    The router scores every expert (``router`` [H, E]); ``held`` names the
    ones whose weights ``experts`` = (wg, wu [n_held, H, F], wd [n_held,
    F, H]) are, in that order.  The step's assignments are sorted by
    expert; those of held experts are cut into blocks of ``EXPERT_BLOCK``
    rows, an expert's last block filled up so that no block holds two,
    and each block is one expert's three plain products.  The blocks in
    use are as many as the data fill (the mean need is ``k * n_held / E``
    assignments a position, the worst case ``min(k, n_held)``: a router
    may send every token here, and while a pass's rows are new it does):
    **no assignment is dropped**, no row is multiplied for an expert it
    did not choose, and a step pays for the blocks its data need.  An
    assignment to an expert that lies elsewhere costs nothing here and
    nothing stands in for it (on several chips the exchange would carry
    it away).  A position outside ``live`` [T], or whose x is all zero
    (every expert returns 0 for it, and its scores tie), is not
    dispatched.

    Returns the output [T, H] and counts: ``held`` assignments to held
    experts, ``dropped`` of them that no block took (``held`` less the
    rows the blocks counted as they ran: 0), ``load`` [n_held] tokens
    each expert received."""
    n_held = len(held)
    size = min(EXPERT_BLOCK, x.shape[0] * top_k)
    with trace.device_scope("router"):
        idx, w = route_top_k(x, router, bias, top_k, scale)
    with trace.device_scope("dispatch"):
        # a held expert's place in ``experts``; n_held: it lies elsewhere
        local = jnp.full((router.shape[1],), n_held, jnp.int32).at[
            jnp.asarray(held)].set(jnp.arange(n_held, dtype=jnp.int32))
        live = live & jnp.any(x != 0, axis=-1)
        key = jnp.where(live[:, None], local[idx], n_held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        load = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0
                       ).astype(jnp.int32)
        # the first block of each expert, and past the last: all in use
        first_block = jnp.concatenate([
            jnp.zeros((1,), jnp.int32), jnp.cumsum(-(-load // size))])
    out, taken = _expert_blocks(x, w.reshape(-1), *experts,
                                (order, load, first_block), size, top_k)
    held_n = jnp.sum(load).astype(jnp.float32)
    counts = {"held": held_n, "dropped": held_n - taken,
              "load": load.astype(jnp.float32)}
    return out, counts
