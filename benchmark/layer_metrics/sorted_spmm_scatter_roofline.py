"""Layer ``kernels``: the scatter-add kernel's share of its roofline
(``opsbytes.scatter_add``; memory bandwidth binds)."""

from benchmark.harness import kernels


def read(run):
    return kernels.roofline_percent(run, kernels.SCATTER)
