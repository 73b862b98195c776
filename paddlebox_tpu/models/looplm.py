"""Looped language model as a sequence tower over pulled token rows.

≙ Ouro ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): a stack of pre/post-normalised transformer layers that
is run ``ut_steps`` times with the SAME weights, an exit gate after each
run, and the entropy-regularised expected next-token loss of the paper's
first stage.  The token embedding is not a dense parameter here: a
token's row is the ``mf`` of its key in the parameter-server table, pulled
per position (``mxu_path.pull_rows``) and trained by the sparse rule; the
layers, the head and the gate are the dense parameters.

Unlike the pooled CTR towers, the model owns its loss.  It declares so on
the class (``row_inputs``) and the trainer hands it what it declares:

    loss(params, rows [B, S, L, D], lengths [B, S], valid [B], **extras)
        -> (loss, aux)

``rows`` are the slots' created-masked ``mf`` rows, position by position,
zeros beyond a slot's length; ``extras`` holds ``seq_keys`` [B, L], the
raw keys of sparse slot ``seq_key_slot``, the slot whose rows are the
sequence (next-token targets are
vocabulary ids, key - ``key_base``; working-set row ids change every
pass).  ``aux`` carries the pairs for the trainer's AUC accumulator
(``auc_pred``, ``auc_label``, ``auc_mask``) and ``stats``, a small vector
(``STATS``) that the trainer sums over a pass's steps and hands back to
``record_stats``, which keeps the ``tower.*`` counters.

Equations (one sequence of n tokens, x_i the row of token i):

    RMS(z; g) = g * z / sqrt(mean(z^2) + eps)   (no gradient through an
                                                  all-zero z: rms_norm)
    layer:  a = RMS(h; g1);  q, k, v = a Wq, a Wk, a Wv  (heads x head_dim)
            q, k = rope(q, i), rope(k, i)       rotate-half, position i
            o = softmax(q k^T / sqrt(head_dim) + causal) v   keys j <= i,
                                                              j < length
            h1 = h + RMS(o Wo; g2);  b = RMS(h1; g3)
            h2 = h1 + RMS((silu(b Wg) * (b Wu)) Wd; g4)
    step t: h(t) = RMS(layers(h(t-1)); gf),  h(0) = x
            z(t) = h(t) W_head;  lam_t = sigmoid(h(t) . w_gate + b_gate)
    exit:   p_t = lam_t prod_{j<t}(1 - lam_j),  p_T = prod_{j<T}(1 - lam_j)
    loss = mean over target positions of
           [sum_t p_t ce_t - beta H(p)],  ce_t = -log softmax(z(t))[y]

with y_i = token_{i+1}; the last valid position has no target.  The
next-item AUC: for every target position the positive y_i and one
negative drawn uniformly from the vocabulary by a counter hash, scored
``sigmoid(log p_T(.) + ln V)`` with p_T the last step's softmax.

The layers' parameters are stacked on a leading axis and the tower is one
``lax.scan`` over the steps x layers applications with a
``jax.checkpoint`` around its body (``tower_terms`` says why not two
nested scans), so the step compiles one layer body and keeps one
activation per layer application; the head is computed in token blocks
under a checkpoint, so no [tokens, vocabulary] array outlives a block.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddlebox_tpu.models import rowlm
from paddlebox_tpu.models.rowlm import rms_norm, sampled_negatives  # noqa: F401
from paddlebox_tpu.utils import trace
from paddlebox_tpu.utils.monitor import stat_add, stat_set

STATS = ("targets", "exit_expected_step_sum", "tokens_valid",
         "tokens_padded")
_NEG = -1e30          # finite "minus infinity": a fully masked row stays finite
ATTN_CHUNK = 2        # sequences a block of attention (scores [c, heads, n, n])
HEAD_BLOCK = 1024     # tokens a block of the head (logits [block, vocabulary])


def rope_tables(n: int, head_dim: int, theta: float):
    """cos, sin [n, head_dim] of the rotate-half form."""
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """x [..., n, heads, head_dim]; cos/sin [n, head_dim]."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


class LoopLM:
    row_inputs = True                 # takes unpooled rows, owns its loss
    extra_inputs = ("seq_keys",)
    seq_key_slot = 0                  # the sparse slot whose rows are the
                                      # sequence and fill the seq_keys plane

    def __init__(self, hidden: int, heads: int, head_dim: int, ffn: int,
                 layers: int, ut_steps: int, vocab: int,
                 rope_theta: float = 1e6, eps: float = 1e-6,
                 beta: float = 0.1, init_std: float = 0.02,
                 key_base: int = 1, neg_seed: int = 0):
        self.hidden, self.heads, self.head_dim = hidden, heads, head_dim
        self.ffn, self.layers, self.ut_steps = ffn, layers, ut_steps
        self.vocab, self.rope_theta, self.eps = vocab, rope_theta, eps
        self.beta, self.init_std = beta, init_std
        self.key_base = key_base      # token id = key - key_base
        self.neg_seed = neg_seed

    # -- parameters ---------------------------------------------------------
    def init(self, key):
        h, f, n = self.hidden, self.ffn, self.layers
        a = self.heads * self.head_dim
        ks = jax.random.split(key, 8)

        def w(k, *shape):
            return self.init_std * jax.random.normal(k, shape, jnp.float32)

        def ones():     # one buffer each: the step donates every leaf
            return jnp.ones((n, h), jnp.float32)

        return {
            "layers": {"wq": w(ks[0], n, h, a), "wk": w(ks[1], n, h, a),
                       "wv": w(ks[2], n, h, a), "wo": w(ks[3], n, a, h),
                       "wg": w(ks[4], n, h, f), "wu": w(ks[5], n, h, f),
                       "wd": w(ks[6], n, f, h),
                       "g1": ones(), "g2": ones(), "g3": ones(),
                       "g4": ones()},
            "gf": jnp.ones((h,), jnp.float32),
            "head": w(ks[7], h, self.vocab),
            "gate_w": jnp.zeros((h,), jnp.float32),
            "gate_b": jnp.zeros((), jnp.float32),
        }

    # -- the tower ------------------------------------------------------------
    def attention(self, q, k, v, keep):
        """softmax(q k^T / sqrt(head_dim) + mask) v on [B, n, heads,
        head_dim], a few sequences at a time under a checkpoint: the
        [heads, n, n] scores of a block are recomputed in the backward
        and no two blocks' are alive together."""
        @jax.checkpoint
        def block(args):
            qb, kb, vb, mb = args
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) \
                / math.sqrt(self.head_dim)
            p = jax.nn.softmax(jnp.where(mb[:, None], s, _NEG), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, vb)

        b = q.shape[0]
        c = math.gcd(b, ATTN_CHUNK)
        if c == b:
            return block((q, k, v, keep))
        split = lambda a: a.reshape((b // c, c) + a.shape[1:])  # noqa: E731
        out = jax.lax.map(block, (split(q), split(k), split(v), split(keep)))
        return out.reshape(q.shape)

    def layer(self, w, h, cos, sin, keep):
        """One layer on h [B, n, H]; keep [B, n, n] attention mask."""
        b, n, _ = h.shape
        nh, hd, eps = self.heads, self.head_dim, self.eps
        a = rms_norm(h, w["g1"], eps)
        q = rope((a @ w["wq"]).reshape(b, n, nh, hd), cos, sin)
        k = rope((a @ w["wk"]).reshape(b, n, nh, hd), cos, sin)
        v = (a @ w["wv"]).reshape(b, n, nh, hd)
        o = self.attention(q, k, v, keep).reshape(b, n, nh * hd)
        h1 = h + rms_norm(o @ w["wo"], w["g2"], eps)
        c = rms_norm(h1, w["g3"], eps)
        m = (jax.nn.silu(c @ w["wg"]) * (c @ w["wu"])) @ w["wd"]
        return h1 + rms_norm(m, w["g4"], eps)

    def head_terms(self, params, h, targets, negatives):
        """One recurrent step's h [M, H], targets/negatives [M] -> its
        cross-entropy [M], gate logit [M], log p of target and of
        negative [M].  Token blocks under a checkpoint: the backward
        recomputes a block's logits instead of keeping [M, vocabulary]."""
        @jax.checkpoint
        def block(args):
            hb, yb, nb = args                      # [blk, H], [blk], [blk]
            lse, zy, zn = rowlm.head_logits(params["head"], hb, yb, nb)
            # one column: kept exact, so that it does not depend on
            # whether the compiler takes it to the MXU
            gate = jnp.dot(hb, params["gate_w"],
                           precision=jax.lax.Precision.HIGHEST) \
                + params["gate_b"]
            return lse - zy, gate, zy - lse, zn - lse

        return rowlm.map_token_blocks(block, HEAD_BLOCK, h, targets,
                                      negatives)

    def tower_terms(self, params, x, lengths, targets, negatives):
        """x [B, n, H] -> ce [T, M], gate logits [T, M] and the last
        step's log p of target and negative [2, M], M = B * n.

        ONE scan over the T * L layer applications (application i runs
        layer i mod L; the one that closes a recurrent step also runs the
        final norm, the head and the gate), its body under a checkpoint:
        the scan keeps one h per application, and the gradient of each
        weight has one accumulator.  As a scan over layers inside a scan
        over steps the same arithmetic held two (inner and outer carry):
        3.3 GB more at Ouro-2.6B's widths."""
        b, n, hd = x.shape
        m, nl, ns = b * n, self.layers, self.ut_steps
        cos, sin = rope_tables(n, self.head_dim, self.rope_theta)
        pos = jnp.arange(n)
        keep = ((pos[None, :, None] >= pos[None, None, :])
                & (pos[None, None, :] < lengths[:, None, None]))

        def close_step(h):
            h = rms_norm(h, params["gf"], self.eps)
            ce, gate, lp_pos, lp_neg = self.head_terms(
                params, h.reshape(m, hd), targets, negatives)
            return h, ce, gate, jnp.stack([lp_pos, lp_neg])

        def go_on(h):
            zero = jnp.zeros((m,), jnp.float32)
            return h, zero, zero, jnp.zeros((2, m), jnp.float32)

        @jax.checkpoint
        def application(carry, i):
            h, ce, gate, _ = carry
            # the layer's weights are sliced out of the stacked tree by
            # the scanned index: as the scan's xs they would be saved
            # again as residuals
            w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i % nl, 0, keepdims=False), params["layers"])
            h = self.layer(w, h, cos, sin, keep)
            h, c, g, lp = jax.lax.cond(i % nl == nl - 1, close_step, go_on,
                                       h)
            at = (jnp.arange(ns) == i // nl)[:, None]      # this step's row
            return (h, ce + jnp.where(at, c[None], 0.0),
                    gate + jnp.where(at, g[None], 0.0), lp), None

        zeros = jnp.zeros((ns, m), jnp.float32)
        with trace.device_scope("tower.ut"):
            (_, ce, gate, lp), _ = jax.lax.scan(
                application,
                (x, zeros, zeros, jnp.zeros((2, m), jnp.float32)),
                jnp.arange(ns * nl))
        return ce, gate, lp

    def exit_log_probs(self, gate):
        """gate logits [T, M] -> log p_t [T, M] of the exit distribution."""
        log_stay = jax.nn.log_sigmoid(-gate)               # log(1 - lam_t)
        before = jnp.cumsum(log_stay, axis=0) - log_stay   # sum_{j<t}
        log_p = jax.nn.log_sigmoid(gate) + before
        return log_p.at[-1].set(before[-1])

    def loss(self, params, rows, lengths, valid, seq_keys):
        x = rows[:, self.seq_key_slot]                        # [B, n, H]
        ln = lengths[:, self.seq_key_slot]
        b, n, h = x.shape
        targets, has_target, negatives = rowlm.next_token_plan(
            seq_keys, ln, valid, n, self.key_base, self.vocab, self.neg_seed)
        ce, gate, (lp_pos, lp_neg) = self.tower_terms(
            params, x, ln, targets.reshape(-1), negatives.reshape(-1))
        with trace.device_scope("tower.head_loss"):
            log_p = self.exit_log_probs(gate)
            p = jnp.exp(log_p)
            per = jnp.sum(p * ce, axis=0) + self.beta * jnp.sum(
                p * log_p, axis=0)                         # - beta * H(p)
            w = has_target.astype(jnp.float32)
            count = jnp.sum(w)
            loss = jnp.sum(per * w) / jnp.maximum(count, 1.0)
            steps = jnp.arange(1, self.ut_steps + 1, dtype=jnp.float32)
            expected = jnp.sum(jnp.sum(p * steps[:, None], axis=0) * w)
            tokens = rowlm.token_counts(ln, valid, n)
            aux = {
                **rowlm.auc_pairs(lp_pos, lp_neg, has_target, self.vocab),
                "stats": jnp.stack([count, expected, tokens,
                                    b * n - tokens]),
            }
        return loss, jax.lax.stop_gradient(aux)

    def record_stats(self, total, steps: int) -> None:
        """Counters of a pass: ``total`` is ``stats`` summed over its
        ``steps`` steps (the trainer reads it back once a pass)."""
        targets, expected, valid, padded = (float(v) for v in total)
        rowlm.record_padding(valid, padded)
        stat_add("tower.recurrent_steps", float(self.ut_steps * steps))
        stat_set("tower.exit_expected_step", expected / max(targets, 1.0))
