"""Operations a hybrid linear-attention training step needs, from shapes
and from the routed layers' own count of what they multiplied: the
numerator of ``step.mfu`` in ``long_seq_epochs`` cells.

As ``harness/flops.py`` counts them: the model's arithmetic over VALID
tokens, forward plus backward (three forwards), no recomputation, no
padded position, the causal half of attention.  Multiply-add = 2.

A sequence of ``n`` valid tokens (H hidden, A = heads x d of KDA):

* a KDA layer, a token: projections ``2 * (3*H*A + 2*(H*r + r*A) + H*heads
  + A*H)`` (q, k, v; the decay's and the output gate's low-rank pairs;
  beta; out), the three short convolutions ``2 * 3 * A * kernel``, and
  the recurrence as ``d x d`` state operations a head: decay ``d*d``,
  ``S^T k``, the rank-one update and ``S^T q`` at ``2*d*d`` each, ``7 *
  heads * d * d``.  The chunked form the program runs spends more (the
  chunk-local products); that is how it is computed, not what the model
  needs;
* the latent-attention layer, a token: projections ``2 * (H*heads*(nope +
  rope) + H*(rank + rope) + rank*heads*(nope + v) + heads*v*H)``; token
  ``i`` meets ``i + 1`` keys: ``heads * (nope + rope + v) * n * (n + 1)``
  a sequence;
* a dense feed-forward ``2 * 3*H*F`` a token; a routed one ``2 * (H*E +
  shared * 3*H*Fe)`` a token (the router over all E experts, the shared
  expert) and ``2 * 3*H*Fe`` an assignment **that a held expert
  received** (``tower.moe.assignments_held``: an assignment to an expert
  that lies elsewhere is not this chip's work);
* the head ``2 * H * V`` a target position (``n - 1``).
"""

from __future__ import annotations

from typing import Iterable


def per_token(*, hidden: int, layers, kda_heads: int, kda_dim: int,
              conv_kernel: int, gate_rank: int, mla_heads: int,
              kv_rank: int, nope: int, rope: int, v_dim: int, ffn: int,
              experts: int, expert_ffn: int, shared: int, vocab: int
              ) -> float:
    """Forward operations a valid token costs whatever its place: the
    layers' projections, convolutions, recurrence and feed-forwards."""
    h, a = hidden, kda_heads * kda_dim
    kda = 2.0 * (3 * h * a + 2 * (h * gate_rank + gate_rank * a)
                 + h * kda_heads + a * h) \
        + 2.0 * 3 * a * conv_kernel + 7.0 * kda_heads * kda_dim * kda_dim
    mla = 2.0 * (h * mla_heads * (nope + rope) + h * (kv_rank + rope)
                 + kv_rank * mla_heads * (nope + v_dim)
                 + mla_heads * v_dim * h)
    dense = 2.0 * 3 * h * ffn
    routed = 2.0 * (h * experts + shared * 3 * h * expert_ffn)
    return sum((kda if mixer == "kda" else mla)
               + (dense if f == "dense" else routed) for mixer, f in layers)


def hybrid_forward(n: int, **sizes) -> float:
    """Forward operations of one sequence of ``n`` valid tokens, without
    the held experts' assignments."""
    if n <= 0:
        return 0.0
    attention = sum(mixer == "mla" for mixer, _ in sizes["layers"]) \
        * sizes["mla_heads"] * (sizes["nope"] + sizes["rope"]
                                + sizes["v_dim"]) * float(n) * (n + 1)
    head = 2.0 * sizes["hidden"] * sizes["vocab"] * (n - 1)
    return per_token(**sizes) * n + attention + head


def hybrid_step(lengths: Iterable[int], assignments_held: float,
                **sizes) -> float:
    """Forward + backward operations of a step over these sequences, its
    routed layers having multiplied ``assignments_held`` assignments."""
    routed = 2.0 * 3 * sizes["hidden"] * sizes["expert_ffn"] \
        * float(assignments_held)
    return 3.0 * (sum(hybrid_forward(int(n), **sizes) for n in lengths)
                  + routed)


def layer_kinds(cfg: dict) -> tuple:
    """(mixer, feed-forward) of the configuration's layers 1 ..
    ``num_hidden_layers``: ``kda`` where ``linear_attn_config.kda_layers``
    lists the layer, else ``mla``; ``dense`` up to
    ``first_k_dense_replace``, then ``moe``."""
    kda = cfg["linear_attn_config"]["kda_layers"]
    return tuple(
        ("kda" if l in kda else "mla",
         "dense" if l <= int(cfg["first_k_dense_replace"]) else "moe")
        for l in range(1, int(cfg["num_hidden_layers"]) + 1))


def hybrid_sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return {
        "hidden": int(cfg["hidden_size"]),
        "layers": layer_kinds(cfg),
        "kda_heads": int(lin["num_heads"]), "kda_dim": int(lin["head_dim"]),
        "conv_kernel": int(lin["short_conv_kernel_size"]),
        "gate_rank": int(cfg["kda"]["gate_rank"]),
        "mla_heads": int(cfg["num_attention_heads"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "ffn": int(cfg["intermediate_size"]),
        "experts": int(cfg["published"]["num_experts"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["num_shared_experts"]),
        "vocab": int(cfg["vocab_size"])}
