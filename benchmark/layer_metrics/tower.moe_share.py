"""Layer ``tower`` (models/hybridlm.py): the share of the step's device
time under the ``tower.moe`` named scope, forward and backward
(``harness/scope_share.py``)."""

from benchmark.harness import scope_share


def read(run):
    return scope_share.read(run, "tower.moe")
