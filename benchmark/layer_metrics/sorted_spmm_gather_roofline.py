"""Layer ``kernels``: the gather kernel's share of its roofline: the
least time the chip could take for the job (``opsbytes.gather`` over the
peaks of ``peaks.json``; memory bandwidth is the roof that binds) over
the kernel's device time a step."""

from benchmark.harness import kernels


def read(run):
    return kernels.roofline_percent(run, kernels.GATHER)
