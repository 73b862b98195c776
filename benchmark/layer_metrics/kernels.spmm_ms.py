"""Layer ``kernels`` (ops/sorted_spmm.py): device time of the two Mosaic
kernels a step, gather plus scatter, found in the trace by kernel name;
mean over the traced window's steps and the cell's chips."""

from benchmark.harness import kernels


def read(run):
    times = [kernels.seconds_per_step(run, name) for name in kernels.NAMES]
    if any(t is None for t in times):
        return None
    return 1e3 * sum(times)
