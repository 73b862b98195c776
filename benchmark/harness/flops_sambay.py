"""Operations a decoder-hybrid-decoder training step needs, from shapes:
the numerator of ``step.mfu`` in ``window_seq_epochs`` cells.

As ``harness/flops.py`` counts them: the model's arithmetic over VALID
tokens, forward plus backward (three forwards), no recomputation, no
padded position.  Multiply-add = 2.  A sequence of ``n`` valid tokens (H
hidden, A = heads x d, K = kv heads x d, Di = d_inner, N = d_state, r =
dt_rank, F the feed-forward's width, V the held vocabulary):

* every layer's feed-forward ``2 * 3*H*F`` a token;
* a Mamba layer, a token: projections ``2 * (H*2*Di + Di*(r + 2*N) +
  r*Di + Di*H)``, the convolution ``2 * Di * kernel``, and the scan as
  ``Di x N`` state updates of 7 operations each: the decay's exponent
  and exponential (2), the drive ``(dt x) B`` (1), ``s = a s + b`` (2) and
  the read-out ``s C`` (2), which is also what the program's
  unrolled chunks of the recurrence spend forward (its checkpoints then
  recompute; that is how it is computed, not what the model needs);
* a GMU ``2 * (H*Di + Di*H)`` a token;
* an attention layer, a token: ``2 * (H*(A + 2*K) + A*H)``, a cross layer
  ``2 * (H*A + A*H)`` (queries and output only).  A (query, key) pair
  costs both softmax maps of every differential head: ``heads`` products
  ``q . k`` over d and ``heads / 2`` subtracted maps times a value of 2d,
  ``2 * heads * d + 2 * (heads / 2) * 2d = 4 * heads * d``.  Token ``i``
  meets ``i + 1`` keys under full and cross attention (the causal half,
  ``n (n + 1) / 2`` pairs) and ``min(i + 1, window)`` under the window
  (the window's keys only);
* the head ``2 * H * V`` a target position (``n - 1``), over the held
  slice.
"""

from __future__ import annotations

from typing import Iterable


def layer_kinds(cfg: dict) -> tuple:
    """(published index, kind) of the layers run here: the published
    model's layers ``share.layer_first`` on, ``num_hidden_layers`` of
    them.  Of the published ``P`` layers the even ones are Mamba up to
    ``P / 2`` and Gated Memory Units beyond; the odd ones differential
    attention over the window below ``P / 2 + 1``, full at ``P / 2 + 1``
    (it gives the shared KV), cross beyond."""
    total = int(cfg["published"]["num_hidden_layers"])
    per = int(cfg["mb_per_layer"])
    first = int(cfg["share"]["layer_first"])
    out = []
    for l in range(first, first + int(cfg["num_hidden_layers"])):
        if l % per == 0:
            kind = "mamba" if l <= total // 2 else "gmu"
        elif l < total // 2 + 1:
            kind = "swa"
        else:
            kind = "attn_full" if l == total // 2 + 1 else "attn_cross"
        out.append((l, kind))
    return tuple(out)


def sambay_sizes(cfg: dict) -> dict:
    m = cfg["mamba"]
    heads = int(cfg["num_attention_heads"])
    return {
        "hidden": int(cfg["hidden_size"]),
        "kinds": tuple(k for _, k in layer_kinds(cfg)),
        "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["hidden_size"]) // heads,
        "window": int(cfg["sliding_window"]),
        "ffn": int(cfg["intermediate_size"]),
        "d_inner": int(m["expand"]) * int(cfg["hidden_size"]),
        "d_state": int(m["d_state"]), "dt_rank": int(m["dt_rank"]),
        "conv_kernel": int(m["d_conv"]),
        "vocab": int(cfg["vocab_size"])}


def per_token(*, hidden, kinds, heads, kv_heads, head_dim, ffn, d_inner,
              d_state, dt_rank, conv_kernel, **_) -> float:
    """Forward operations a valid token costs whatever its place."""
    h, a, k = hidden, heads * head_dim, kv_heads * head_dim
    cost = {
        "mamba": 2.0 * (h * 2 * d_inner + d_inner * (dt_rank + 2 * d_state)
                        + dt_rank * d_inner + d_inner * h)
        + 2.0 * d_inner * conv_kernel + 7.0 * d_inner * d_state,
        "gmu": 2.0 * (h * d_inner + d_inner * h),
        "swa": 2.0 * (h * (a + 2 * k) + a * h),
        "attn_full": 2.0 * (h * (a + 2 * k) + a * h),
        "attn_cross": 2.0 * (h * a + a * h)}
    return sum(cost[kind] + 2.0 * 3 * h * ffn for kind in kinds)


def attention_pairs(n: int, window: int = 0) -> float:
    """(query, key) pairs of a causal layer over ``n`` tokens: token i
    meets i + 1 keys, at most ``window`` of them under a window."""
    if not window or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + float(n - window) * window


def sambay_forward(n: int, **sizes) -> float:
    """Forward operations of one sequence of ``n`` valid tokens."""
    if n <= 0:
        return 0.0
    kinds = sizes["kinds"]
    pair = 4.0 * sizes["heads"] * sizes["head_dim"]
    attention = pair * (
        sum(k in ("attn_full", "attn_cross") for k in kinds)
        * attention_pairs(n)
        + sum(k == "swa" for k in kinds)
        * attention_pairs(n, sizes["window"]))
    head = 2.0 * sizes["hidden"] * sizes["vocab"] * (n - 1)
    return per_token(**sizes) * n + attention + head


def sambay_step(lengths: Iterable[int], **sizes) -> float:
    """Forward + backward operations of a step over these sequences."""
    return 3.0 * sum(sambay_forward(int(n), **sizes) for n in lengths)
