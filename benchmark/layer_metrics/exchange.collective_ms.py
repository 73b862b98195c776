"""Layer ``exchange`` (ps/sharded_embedding.py): milliseconds a step in
which an all-gather or a reduce-scatter is in flight on a chip, from the
collective events of the trace; mean over the cell's chips."""

from benchmark.harness import xplane


def read(run):
    if run.trace is None or run.trace_window is None or run.chips < 2:
        return None
    planes = xplane.device_planes(run.trace)[:run.chips]
    steps = [len(run.step_runs(p)) for p in planes]
    if not planes or not all(steps):
        return None
    flight = [xplane.collective_seconds(run.trace, p, run.trace_window)[0]
              for p in planes]
    return 1e3 * sum(f / n for f, n in zip(flight, steps)) / len(planes)
