"""Layer ``exchange``: the share of the collectives' time in which no
other operation runs on that chip: what overlap would hide."""

from benchmark.harness import xplane


def read(run):
    if run.trace is None or run.trace_window is None or run.chips < 2:
        return None
    flight = exposed = 0.0
    for plane in xplane.device_planes(run.trace)[:run.chips]:
        f, e = xplane.collective_seconds(run.trace, plane, run.trace_window)
        flight, exposed = flight + f, exposed + e
    return 100.0 * exposed / flight if flight else None
