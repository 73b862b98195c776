"""Layer ``tower`` (models/sambay.py): the share of the step's device
time under the ``tower.mamba`` named scope, forward and backward:
the two Mamba layers' mixers (projections, convolution, the chunked selective scan, the gate)
(``harness/scope_share.py``)."""

from benchmark.harness import scope_share


def read(run):
    return scope_share.read(run, "tower.mamba")
