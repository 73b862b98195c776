"""Operations a looped-language-model training step needs, from shapes:
the numerator of ``step.mfu``.

What is counted is the model's arithmetic over VALID tokens, as the
``on-chip-measurement`` guide defines model FLOPs: forward plus backward
(three forwards), no recomputation (the program checkpoints a layer and
recomputes it: that is overhead, not work), no padded position, and the
causal half of attention (a token attends to itself and what precedes
it).  Multiply-add = 2 operations.

A sequence of ``n`` valid tokens, one recurrent step of ``layers`` layers:

* projections and feed-forward: ``2 * (4*H*A + 3*H*F)`` a token a layer
  (``A`` = heads x head_dim; q, k, v, o and gate, up, down);
* attention: token ``i`` multiplies against ``i + 1`` keys twice (scores,
  then values): ``4 * A * (i + 1)``, ``2 * A * n * (n + 1)`` a sequence;
* head and exit gate: ``2 * H * (V + 1)`` a target position (``n - 1``).
"""

from __future__ import annotations

from typing import Iterable


def looplm_forward(n: int, *, hidden: int, heads: int, head_dim: int,
                   ffn: int, layers: int, steps: int, vocab: int) -> float:
    """Forward operations of one sequence of ``n`` valid tokens."""
    if n <= 0:
        return 0.0
    a = heads * head_dim
    layer = 2.0 * (4 * hidden * a + 3 * hidden * ffn) * n \
        + 2.0 * a * n * (n + 1)
    head = 2.0 * hidden * (vocab + 1) * (n - 1)
    return steps * (layers * layer + head)


def looplm_step(lengths: Iterable[int], **sizes) -> float:
    """Forward + backward operations of a step over these sequences."""
    return 3.0 * sum(looplm_forward(int(n), **sizes) for n in lengths)


def looplm_sizes(cfg: dict) -> dict:
    return {"hidden": int(cfg["hidden_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "ffn": int(cfg["intermediate_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "steps": int(cfg["total_ut_steps"]),
            "vocab": int(cfg["vocab_size"])}
