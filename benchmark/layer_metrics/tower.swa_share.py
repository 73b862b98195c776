"""Layer ``tower`` (models/sambay.py): the share of the step's device
time under the ``tower.swa`` named scope, forward and backward:
the window-attention layer's mixer (projections, the sliced 512-key blocks, both softmax maps)
(``harness/scope_share.py``)."""

from benchmark.harness import scope_share


def read(run):
    return scope_share.read(run, "tower.swa")
