"""Layer ``tower`` (models/sambay.py): the share of the step's device
time under the ``tower.attn_full`` and ``tower.attn_cross`` named scopes,
forward and backward: the two layers whose queries meet every earlier
key of the sequence (the full-attention layer that gives the shared KV,
the cross-attention layer that reads it).  Each scope's share is read by
``harness/scope_share.py``; the two hold different instructions of one
device stream, so their shares add."""

from benchmark.harness import scope_share


def read(run):
    parts = [scope_share.read(run, scope)
             for scope in ("tower.attn_full", "tower.attn_cross")]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None
