"""Layer ``step``: model FLOP/s utilisation of the whole train step: the
operations the model's forward and backward need over the step's valid
tokens (``harness/flops.py``: no recomputation, no padded position, the
causal half of attention), mean over the pass's steps, over the median
device time of a ``jit_step`` run and the chip's bf16 peak."""

import statistics

from benchmark.harness import xplane


def read(run):
    flops = run.geometry.get("model_flops_per_step")
    if not flops or run.trace is None or run.trace_window is None \
            or run.peaks is None:
        return None
    runs = [e.end - e.start
            for plane in xplane.device_planes(run.trace)[:run.chips]
            for e in run.step_runs(plane)]
    if not runs:
        return None
    seconds = statistics.median(runs) / 1e9
    return 100.0 * flops / seconds / run.peaks["bf16_flops"]
