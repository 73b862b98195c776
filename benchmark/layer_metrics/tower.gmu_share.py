"""Layer ``tower`` (models/sambay.py): the share of the step's device
time under the ``tower.gmu`` named scope, forward and backward:
the Gated Memory Unit's mixer (two projections and the gate over the shared memory)
(``harness/scope_share.py``)."""

from benchmark.harness import scope_share


def read(run):
    return scope_share.read(run, "tower.gmu")
