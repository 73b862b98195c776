"""Layer ``device``: the share of the traced window in which no
operation ran on the chip: 1 - (union of the device's operation
intervals) / window, mean over the cell's chips."""

from benchmark.harness import xplane


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    win = run.trace_window
    busy = xplane.busy_seconds(run.trace, win)[:run.chips]
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / ((win[1] - win[0]) / 1e9))
