"""What the row language models share (``looplm.py``, ``hybridlm.py``):
towers over token rows pulled per position that own a next-token loss.

The norm, the sampled negatives, the targets of a batch, the head in
token blocks, the next-item AUC's pairs and the padding counters.  A
model's own file holds its layers and how it combines these.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddlebox_tpu.utils import trace
from paddlebox_tpu.utils.monitor import stat_add


def rms_norm(z, g, eps):
    """RMS(z; g).  An all-zero vector (a row the table has not created
    yet, a position that saw nothing but such rows) passes no gradient:
    the norm's Jacobian there is g / sqrt(eps), and a chain of them, one
    a norm down a position that stays zero, overflows float32 into NaN
    parameter gradients, where the true contribution (0 x finite) is 0."""
    dead = jnp.all(z == 0, axis=-1, keepdims=True)
    z = jnp.where(dead, jax.lax.stop_gradient(z), z)
    return g * z * jax.lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True)
                                 + eps)


def sampled_negatives(seed: int, first_key, lengths, n: int, vocab: int):
    """One vocabulary id a (example, position), uniform, from a counter
    hash of (seed, example, position); an example is named by its place
    in the batch, its first key and its length, so batches differ.
    first_key, lengths [B] int32 -> [B, n] int32."""
    def mix(h):
        h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
        h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
        return h ^ (h >> 16)

    b = first_key.shape[0]
    ex = mix(jnp.uint32(seed & 0xFFFFFFFF)
             + jnp.arange(b, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1)
             + first_key.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
             + lengths.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    h = mix(ex[:, None] + jnp.arange(n, dtype=jnp.uint32)[None, :]
            * jnp.uint32(0x27D4EB2F))
    return (h % jnp.uint32(vocab)).astype(jnp.int32)


def next_token_plan(seq_keys, ln, valid, n: int, key_base: int, vocab: int,
                    neg_seed: int):
    """What a batch's loss is taken over: ``targets`` [B, n] (token i + 1
    at position i, 0 at the last), ``has_target`` [B * n] (the last valid
    position has none) and the AUC's ``negatives`` [B, n]."""
    b = seq_keys.shape[0]
    tokens = jnp.clip(seq_keys[:, :n] - key_base, 0, vocab - 1)
    pos = jnp.arange(n)
    has_target = ((pos[None, :] < ln[:, None] - 1)
                  & valid[:, None]).reshape(-1)            # [M]
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    negatives = sampled_negatives(neg_seed, seq_keys[:, 0], ln, n, vocab)
    return targets, has_target, negatives


def head_logits(head, hb, yb, nb):
    """A block's logits through ``head`` [H, V]: their log-sum-exp, the
    target's and the negative's, each [blk]."""
    z = hb @ head                                          # [blk, V]
    lse = jax.nn.logsumexp(z, axis=-1)
    zy = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
    zn = jnp.take_along_axis(z, nb[:, None], axis=-1)[:, 0]
    return lse, zy, zn


def map_token_blocks(block, blk: int, h, targets, negatives):
    """``block((hb, yb, nb))`` over blocks of ``blk`` tokens of h [M, H]
    (the tail padded with zeros), each output cut back to [M]."""
    m, hd = h.shape
    blk = min(blk, m)
    pad = -m % blk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        negatives = jnp.pad(negatives, (0, pad))
    with trace.device_scope("tower.head_loss"):
        out = jax.lax.map(block, (h.reshape(-1, blk, hd),
                                  targets.reshape(-1, blk),
                                  negatives.reshape(-1, blk)))
    return tuple(a.reshape(-1)[:m] for a in out)


def token_counts(ln, valid, n: int):
    """Valid tokens of a batch [B] of capacity ``n``, as a float."""
    return jnp.sum(jnp.where(valid, jnp.minimum(ln, n), 0)
                   ).astype(jnp.float32)


def auc_pairs(lp_pos, lp_neg, has_target, vocab: int) -> dict:
    """The next-item AUC's pairs: for every target position the positive
    and its sampled negative, scored ``sigmoid(log p(.) + ln V)``."""
    ln_v = math.log(vocab)
    return {
        "auc_pred": jax.nn.sigmoid(
            jnp.concatenate([lp_pos, lp_neg]) + ln_v),
        "auc_label": jnp.concatenate(
            [jnp.ones_like(lp_pos), jnp.zeros_like(lp_neg)]),
        "auc_mask": jnp.concatenate([has_target, has_target]),
    }


def record_padding(valid: float, padded: float) -> None:
    """The ``tower.*`` padding counters of a pass."""
    stat_add("tower.tokens_valid", valid)
    stat_add("tower.tokens_padded", padded)
