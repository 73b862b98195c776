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
    """Block i of the sorted held assignments: its expert, where it starts
    in the sort, the assignments in it (ids into [T * k]), their tokens,
    and which of its rows hold one (an expert's last block is filled up
    with rows that hold none).  ``plan`` = (key, order, load,
    first_block), ``order`` padded by a block, so that a block is one
    slice of it."""
    _, order, load, first_block = plan
    with trace.device_scope("dispatch"):
        e = jnp.sum(first_block <= i) - 1            # the block's expert
        inside = (i - first_block[e]) * size
        keep = inside + jnp.arange(size) < load[e]
        at = jnp.cumsum(load)[e] - load[e] + inside  # place in the sort
        rows = lax.dynamic_slice_in_dim(order, at, size)
    return e, at, rows, rows // top_k, keep


def _expert_rows(xs, wt, wg, wu, wd):
    """One expert's SwiGLU on its rows, times their routing weights."""
    with trace.device_scope("experts"):
        return ((jax.nn.silu(xs @ wg) * (xs @ wu)) @ wd) * wt[:, None]


def _sorted_weights(weights, plan, size: int):
    """The routing weights [T * k] in the order of the sort, padded as
    ``order`` is: a block's weights are then one slice."""
    with trace.device_scope("dispatch"):
        _, w = lax.sort((plan[0], weights), num_keys=1, is_stable=True)
        return jnp.concatenate([w, jnp.zeros((size,), w.dtype)])


def _block_inputs(x, w_sorted, experts, plan, i, size, top_k):
    """What block i needs besides its rows of x: its expert, place in the
    sort, tokens and kept rows (``_block``), their routing weights, the
    expert's weights, and whether the kept rows are the consecutive tokens
    from ``token[0]`` on (as when every live position chose the expert)
    with ``size`` positions from there inside x: such a block moves its
    rows as one slice, any other one row by row (``_take`` / ``_put``)."""
    e, at, _, token, keep = _block(plan, i, size, top_k)
    with trace.device_scope("dispatch"):
        wt = jnp.where(keep, lax.dynamic_slice_in_dim(w_sorted, at, size),
                       0.0)
        w_e = tuple(lax.dynamic_index_in_dim(w, e, keepdims=False)
                    for w in experts)
        kept = jnp.sum(keep)
        sliced = (token[jnp.maximum(kept - 1, 0)] - token[0] == kept - 1) \
            & (token[0] + size <= x.shape[0])
    return e, at, token, keep, wt, w_e, sliced


def _take(a, token, keep, sliced: bool):
    """The block's rows of ``a`` [T, H], 0 where a row holds no
    assignment."""
    with trace.device_scope("dispatch"):
        rows = lax.dynamic_slice_in_dim(a, token[0], keep.shape[0]) \
            if sliced else a[token]
        return jnp.where(keep[:, None], rows, 0.0)


def _put(a, token, keep, ys, sliced: bool):
    """``a`` with the block's kept rows ``ys`` added at their tokens."""
    with trace.device_scope("combine"):
        ys = jnp.where(keep[:, None], ys, 0.0)
        if not sliced:
            return a.at[token].add(ys)
        return lax.dynamic_update_slice_in_dim(
            a, lax.dynamic_slice_in_dim(a, token[0], ys.shape[0]) + ys,
            token[0], 0)


def _by_block(block, sliced, size: int, n: int, carry):
    """``block(True)(carry)`` where the block's rows are one slice of the
    ``n`` positions, else ``block(False)(carry)``; a block of more rows
    than there are positions is never one slice."""
    if size > n:
        return block(False)(carry)
    return lax.cond(sliced, block(True), block(False), carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _expert_blocks(x, weights, wg, wu, wd, plan, size: int, top_k: int):
    """The held assignments, sorted by expert and cut into blocks of
    ``size`` rows none of which holds two experts, multiplied block by
    block: as many blocks as the data fill (a ``while``, so the backward
    is written out below: the same blocks again, each recomputed and
    differentiated alone, an expert's weight gradient added in place, the
    routing weights' gradient laid down in the order of the sort and put
    back in the assignments' by one more sort).  Returns the output
    [T, H] and the assignments the blocks took."""
    w_sorted = _sorted_weights(weights, plan, size)

    def one(i, carry):
        out, taken = carry
        _, _, token, keep, wt, w_e, sliced = _block_inputs(
            x, w_sorted, (wg, wu, wd), plan, i, size, top_k)

        def block(sliced):
            return lambda out: _put(out, token, keep, _expert_rows(
                _take(x, token, keep, sliced), wt, *w_e), sliced)

        out = _by_block(block, sliced, size, x.shape[0], out)
        return out, taken + jnp.sum(keep).astype(jnp.float32)

    return lax.fori_loop(0, plan[3][-1], one,
                         (jnp.zeros_like(x), jnp.zeros((), jnp.float32)))


def _expert_blocks_fwd(x, weights, wg, wu, wd, plan, size, top_k):
    return (_expert_blocks(x, weights, wg, wu, wd, plan, size, top_k),
            (x, weights, wg, wu, wd, plan))


def _expert_blocks_bwd(size, top_k, saved, g):
    x, weights, wg, wu, wd, plan = saved
    w_sorted = _sorted_weights(weights, plan, size)

    def one(i, grads):
        dx, dwt, dw = grads
        e, at, token, keep, wt, w_e, sliced = _block_inputs(
            x, w_sorted, (wg, wu, wd), plan, i, size, top_k)

        def block(sliced):
            def f(dx):
                _, vjp = jax.vjp(_expert_rows, _take(x, token, keep, sliced),
                                 wt, *w_e)
                dxs, dwt_rows, *dw_e = vjp(_take(g[0], token, keep, sliced))
                return _put(dx, token, keep, dxs, sliced), dwt_rows, dw_e
            return f

        dx, dwt_rows, dw_e = _by_block(block, sliced, size, x.shape[0], dx)
        with trace.device_scope("combine"):
            # a block's filler rows write 0 where the next expert's blocks,
            # later in the loop, write their own
            dwt = lax.dynamic_update_slice_in_dim(
                dwt, jnp.where(keep, dwt_rows, 0.0), at, 0)
            dw = tuple(a.at[e].add(b) for a, b in zip(dw, dw_e))
        return dx, dwt, dw

    dx, dwt, dw = lax.fori_loop(
        0, plan[3][-1], one,
        (jnp.zeros_like(x), jnp.zeros_like(w_sorted),
         tuple(jnp.zeros_like(w) for w in (wg, wu, wd))))
    with trace.device_scope("combine"):
        n = weights.shape[0]
        _, dwt = lax.sort((plan[1][:n], dwt[:n]), num_keys=1)
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
    and each block is one expert's three plain products; a block whose
    rows are consecutive tokens (every live position there chose its
    expert: a router that herds makes most blocks so) reads and adds
    them as one slice, any other block row by row; a block's routing
    weights and their gradient are one slice of the sort's order
    always.  The blocks in
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
    each expert received, ``route`` [E] the dispatched positions that
    chose each expert of all of them (what a balancing bias reads)."""
    n_held, n_all = len(held), router.shape[1]
    size = min(EXPERT_BLOCK, x.shape[0] * top_k)
    with trace.device_scope("router"):
        idx, w = route_top_k(x, router, bias, top_k, scale)
    with trace.device_scope("dispatch"):
        # a held expert's place in ``experts``; n_held: it lies elsewhere
        local = jnp.full((n_all,), n_held, jnp.int32).at[
            jnp.asarray(held)].set(jnp.arange(n_held, dtype=jnp.int32))
        live = live & jnp.any(x != 0, axis=-1)
        route = jnp.sum((idx[:, :, None] == jnp.arange(n_all))
                        & live[:, None, None], axis=(0, 1)
                        ).astype(jnp.float32)
        key = jnp.where(live[:, None], local[idx], n_held).reshape(-1)
        order = jnp.concatenate([jnp.argsort(key, stable=True),
                                 jnp.zeros((size,), jnp.int32)])
        load = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0
                       ).astype(jnp.int32)
        # the first block of each expert, and past the last: all in use
        first_block = jnp.concatenate([
            jnp.zeros((1,), jnp.int32), jnp.cumsum(-(-load // size))])
    out, taken = _expert_blocks(x, w.reshape(-1), *experts,
                                (key, order, load, first_block), size,
                                top_k)
    held_n = jnp.sum(load).astype(jnp.float32)
    counts = {"held": held_n, "dropped": held_n - taken,
              "load": load.astype(jnp.float32), "route": route}
    return out, counts
