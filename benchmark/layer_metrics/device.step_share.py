"""Layer ``device``: per cent of the chip's busy time in the traced
window that lies inside runs of the jitted train step (``jit_step`` on
``XLA Modules``); the rest is the feed's programs, the working set's
upload and write-back, and the read-backs."""

from benchmark.harness import xplane


def read(run):
    win = run.trace_window
    if run.trace is None or win is None:
        return None
    step = sum(e.end - e.start
               for plane in xplane.device_planes(run.trace)[:run.chips]
               for e in run.step_runs(plane)) / 1e9
    busy = sum(xplane.busy_seconds(run.trace, win)[:run.chips])
    return 100.0 * step / busy if step and busy else None
