"""Operations an AFMoE training step needs, from shapes and from the routed
layers' own count of what they multiplied: the numerator of ``step.mfu``
for a cell of an AFMoE configuration (``configs/trinity_mini.json``).

As ``harness/flops.py`` counts them: the model's arithmetic over VALID
tokens, forward plus backward (three forwards), no recomputation, no
padded position.  Multiply-add = 2.  A sequence of ``n`` valid tokens (H
hidden, A = heads x d, K = kv heads x d, F the dense feed-forward's
width, Fe an expert's, E the router's width, V the held vocabulary):

* an attention layer, a token: projections ``2 * (H*A + 2*H*K + H*A +
  A*H)`` (q; k and v; the output gate; out).  A (query, key) pair costs
  ``q . k`` and the value's share over d in every head: ``4 * heads *
  d``.  Token ``i`` meets ``min(i + 1, window)`` keys on a sliding layer
  (the window's keys only) and ``i + 1`` on the full layer (the causal
  half); the norms and the rotation are left out, as everywhere here;
* a dense feed-forward ``2 * 3*H*F`` a token; a routed one ``2 * (H*E +
  shared * 3*H*Fe)`` a token (the router over all E experts, the shared
  expert) and ``2 * 3*H*Fe`` an assignment **that a held expert
  received** (``tower.moe.assignments_held``, as the program counted
  them: an assignment to an expert that lies elsewhere is not this
  chip's work);
* the head ``2 * H * V`` a target position (``n - 1``), over the held
  slice.
"""

from __future__ import annotations

from typing import Iterable

from benchmark.harness.flops_sambay import attention_pairs

MIXERS = {"sliding_attention": "swa", "full_attention": "attn_full"}


def layer_kinds(cfg: dict) -> tuple:
    """(mixer, feed-forward) of the published layers run here,
    ``share.layer_first`` on, ``num_hidden_layers`` of them: the mixer
    from ``layer_types`` (``swa`` | ``attn_full``), the feed-forward
    ``dense`` below ``num_dense_layers``, then ``moe``."""
    first = int(cfg["share"]["layer_first"])
    dense = int(cfg["num_dense_layers"])
    return tuple(
        (MIXERS[cfg["layer_types"][l]], "dense" if l < dense else "moe")
        for l in range(first, first + int(cfg["num_hidden_layers"])))


def afmoe_sizes(cfg: dict) -> dict:
    return {
        "hidden": int(cfg["hidden_size"]),
        "layers": layer_kinds(cfg),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "window": int(cfg["sliding_window"]),
        "ffn": int(cfg["intermediate_size"]),
        "experts": int(cfg["published"]["num_experts"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["num_shared_experts"]),
        "vocab": int(cfg["vocab_size"])}


def per_token(*, hidden, layers, heads, kv_heads, head_dim, ffn, experts,
              expert_ffn, shared, **_) -> float:
    """Forward operations a valid token costs whatever its place."""
    h, a, k = hidden, heads * head_dim, kv_heads * head_dim
    attention = 2.0 * (h * a + 2 * h * k + h * a + a * h)
    dense = 2.0 * 3 * h * ffn
    routed = 2.0 * (h * experts + shared * 3 * h * expert_ffn)
    return sum(attention + (dense if f == "dense" else routed)
               for _, f in layers)


def afmoe_forward(n: int, **sizes) -> float:
    """Forward operations of one sequence of ``n`` valid tokens, without
    the held experts' assignments."""
    if n <= 0:
        return 0.0
    mixers = [m for m, _ in sizes["layers"]]
    pair = 4.0 * sizes["heads"] * sizes["head_dim"]
    attention = pair * (
        mixers.count("attn_full") * attention_pairs(n)
        + mixers.count("swa") * attention_pairs(n, sizes["window"]))
    head = 2.0 * sizes["hidden"] * sizes["vocab"] * (n - 1)
    return per_token(**sizes) * n + attention + head


def afmoe_step(lengths: Iterable[int], assignments_held: float,
               **sizes) -> float:
    """Forward + backward operations of a step over these sequences, its
    routed layers having multiplied ``assignments_held`` assignments."""
    routed = 2.0 * 3 * sizes["hidden"] * sizes["expert_ffn"] \
        * float(assignments_held)
    return 3.0 * (sum(afmoe_forward(int(n), **sizes) for n in lengths)
                  + routed)
