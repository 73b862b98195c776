"""Layer ``step`` (trainer._make_core): median device time of one run of
the jitted train step (the ``jit_step`` program on the trace's ``XLA
Modules`` line), over the traced window's steps and the cell's chips."""

import statistics

from benchmark.harness import xplane


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    runs = [e.end - e.start
            for plane in xplane.device_planes(run.trace)[:run.chips]
            for e in run.step_runs(plane)]
    return statistics.median(runs) / 1e6 if runs else None
