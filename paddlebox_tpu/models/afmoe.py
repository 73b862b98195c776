"""AFMoE language model (Arcee Trinity) as a sequence tower over pulled
token rows.

≙ Trinity-Mini (arcee-ai, ``model_type`` ``afmoe``): grouped-query
softmax attention, over a sliding window on three layers of four and over
the whole sequence on the fourth, its output gated and its queries and
keys normalised per head, a norm after each sublayer as well as before
it; a leading dense SwiGLU feed-forward, then sigmoid-routed experts,
top-k of all of them plus one shared, balanced by a bias that a rule of
its own moves after every step (no gradient, no auxiliary loss).  As in
``hybridlm.py`` a token's embedding is the ``mf`` row of its key, pulled
per position and trained by the sparse rule, and the model owns its
next-token loss; the layer loop, the feed-forwards, the head, the loss
and the counters are ``hybridlm.RoutedLM``'s, the attention's blocking
``sambay.py``'s.

Equations (one sequence of n tokens, RMS as ``rowlm.rms_norm``):

    x_i = row(token_i) * input_scale       (muP: sqrt(hidden))
    layer l:
      a   = RMS(h; g1)
      q   = RMS_head(a Wq; g_q) [heads x d],  k = RMS_head(a Wk; g_k)
            [kv x d],  v = a Wv [kv x d]
      sliding layer: q, k <- RoPE(q, k; theta, all d dimensions,
            positions 0.. within the sequence);  full layer: no rotation
      o_h = softmax(q_h k_{h // (heads / kv)}^T / sqrt(d) + M) v_{same},
            M: j <= t, j < length, and on a sliding layer t - j < window
      h'  = h + RMS((o * sigmoid(a Wgate)) Wo; g1_post)
      b   = RMS(h'; g2)
      dense layer:  f = (silu(b Wg) * (b Wu)) Wd
      routed layer: s = sigmoid(b Wr) over all experts (float32);
                    chosen = top-k of s + bias;
                    w_e = scale * s_e / sum_chosen s;
                    f = E_shared(b) + sum over e chosen AND held here of
                        w_e E_e(b)
      h'' = h' + RMS(f; g2_post)
    out = RMS(h_L; gf) W_head;  loss = mean next-token cross-entropy
    after each step, a routed layer each (``RoutedLM.balance_bias``):
      c_e = dispatched positions that chose e (all experts, this chip's
            tokens);  d_e = rate * sign(mean(c) - c_e);
      bias_e += d_e - mean(d)

**Attention runs one kv group at a time** (``grouped_attention``): a
block holds the queries of one kv group's heads, so its scores, [heads /
kv, block, keys] float32, stay in the chip's fast memory (``sambay.py``,
PERF.md section 6).  A sliding layer's block of ``SWA_QBLOCK`` queries
slices its ``window + block`` keys out of the sequence
(``sambay.sliding_blocks``); the full layer's blocks of ``ATTN_QBLOCK``
queries fall into ``ATTN_GROUPS`` groups whose keys end where the group
does (``sambay.causal_blocks``).  A mixer takes one sequence at a time,
each under its own checkpoint.  The held experts' part of a routed layer
is kept from the forward for the backward (``RoutedLM``'s
``keep_routed``: [B * n, H] float32, 134 MB a routed layer at 16,384
positions, which the step's room holds), so a step runs the expert
blocks twice, not three times.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.hybridlm import RoutedLM
from paddlebox_tpu.models.looplm import rope, rope_tables
from paddlebox_tpu.models.rowlm import rms_norm
from paddlebox_tpu.models.sambay import causal_blocks, sliding_blocks

_NEG = -1e30          # finite "minus infinity": a masked row stays finite
SWA_QBLOCK = 256      # queries a block of window attention
ATTN_QBLOCK = 128     # queries a block of full attention
ATTN_GROUPS = 16      # groups of blocks whose keys end with the group
KINDS = ("swa", "attn_full")


def _grouped_block(qb, kb, vb, keep, scale):
    """One block of queries against the keys it is given: qb [1, heads a
    group, q, d], kb, vb [1, k, d], keep [q, k] -> [1, heads a group, q,
    d]."""
    s = jnp.einsum("ghqd,gkd->ghqk", qb, kb) * scale
    p = jax.nn.softmax(jnp.where(keep, s, _NEG), axis=-1)
    return jnp.einsum("ghqk,gkd->ghqd", p, vb)


def grouped_attention(q, k, v, length, window: int = 0):
    """Causal softmax attention of one sequence whose query heads share a
    kv head: q [kv, heads / kv, n, d], k, v [kv, n, d], ``length`` valid
    tokens, over a sliding ``window`` (0: all earlier keys); returns [kv,
    heads / kv, n, d]."""
    scale = 1.0 / math.sqrt(q.shape[-1])

    def attend(qb, kb, vb, keep):
        return _grouped_block(qb, kb, vb, keep, scale)

    if window:
        return sliding_blocks(attend, q, k, v, length, window,
                              qblock=SWA_QBLOCK, gb=1)
    return causal_blocks(attend, q, k, v, length, qblock=ATTN_QBLOCK,
                         groups=ATTN_GROUPS, gb=1)


class AfmoeLM(RoutedLM):
    """Trinity's tower (module docstring): grouped-query attention mixers
    over ``RoutedLM``'s loop, with sandwich norms, the muP input scale
    and the balancing bias's update."""

    def __init__(self, hidden: int, layers: Sequence[Tuple[str, str]],
                 vocab: int, *, heads: int, kv_heads: int, head_dim: int,
                 window: int, rope_theta: float, ffn: int, experts: int,
                 experts_held: Sequence[int], top_k: int, expert_ffn: int,
                 shared_experts: int, routed_scale: float,
                 balance_rate: float, input_scale: float, eps: float = 1e-5,
                 init_std: float = 0.02, key_base: int = 1,
                 neg_seed: int = 0):
        """``layers``: a (mixer, ffn) pair a layer, mixer ``swa`` |
        ``attn_full``, ffn ``dense`` | ``moe``; ``experts`` is the router's
        width, ``experts_held`` the ids of the experts this chip holds;
        ``balance_rate`` the bias rule's step (0: held where it is)."""
        unknown = {m for m, _ in layers} - set(KINDS)
        if unknown or heads % kv_heads:
            raise ValueError(f"mixers {sorted(unknown)} / heads {heads}, "
                             f"{kv_heads}: kinds are {KINDS}, and query "
                             "heads share kv heads evenly")
        super().__init__(
            hidden, layers, vocab, ffn=ffn, experts=experts,
            experts_held=experts_held, top_k=top_k, expert_ffn=expert_ffn,
            shared_experts=shared_experts, routed_scale=routed_scale,
            eps=eps, init_std=init_std, key_base=key_base,
            neg_seed=neg_seed, sandwich=True, input_scale=input_scale,
            balance_rate=balance_rate, keep_routed=True)
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.window, self.rope_theta = window, rope_theta

    def init_mixer(self, kind, w, ones, keys):
        h, d = self.hidden, self.head_dim
        a, kv = self.heads * d, self.kv_heads * d
        return {"wq": w(h, a), "wk": w(h, kv), "wv": w(h, kv),
                "wgate": w(h, a), "g_q": ones(d), "g_k": ones(d),
                "wo": w(a, h)}

    def seqs_a_group(self, kind):
        return 1

    def mixer(self, kind, w, a, lengths):
        return jnp.stack([self.attention(kind, w, a[i], lengths[i])
                          for i in range(a.shape[0])])

    def attention(self, kind, w, a, length):
        """One sequence's attention mixer on a [n, H] (before the
        sublayer's own norm)."""
        n = a.shape[0]
        nh, g, d = self.heads, self.kv_heads, self.head_dim
        q = rms_norm((a @ w["wq"]).reshape(n, nh, d), w["g_q"], self.eps)
        k = rms_norm((a @ w["wk"]).reshape(n, g, d), w["g_k"], self.eps)
        v = (a @ w["wv"]).reshape(n, g, d)
        if kind == "swa":
            cos, sin = rope_tables(n, d, self.rope_theta)
            q, k = rope(q, cos, sin), rope(k, cos, sin)
        # head-major: q [kv, heads / kv, n, d], k and v [kv, n, d]
        o = grouped_attention(
            jnp.transpose(q.reshape(n, g, nh // g, d), (1, 2, 0, 3)),
            jnp.transpose(k, (1, 0, 2)), jnp.transpose(v, (1, 0, 2)),
            length, self.window if kind == "swa" else 0)
        o = jnp.transpose(o, (2, 0, 1, 3)).reshape(n, nh * d)
        return (o * jax.nn.sigmoid(a @ w["wgate"])) @ w["wo"]
