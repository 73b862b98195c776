"""Layer ``feed_build`` (``data/pass_feed.py``): device seconds a pass of
the feed's own programs (the program's ``DEVICE_PROGRAMS``: the relayout
of the uploaded planes and the two plan builders), from their runs on the
trace's ``XLA Modules`` line inside the window.  The span
``data.feed.plans`` times their enqueue; this is what the chip spends."""

from benchmark.harness import step_scopes, xplane


def read(run):
    names = step_scopes.program_names()
    win = run.trace_window
    passes = run.stats.get("data.prefetch.passes") or len(run.units)
    if names is None or run.trace is None or win is None or not passes:
        return None
    took = []
    for plane in xplane.device_planes(run.trace)[:run.chips]:
        by_program = step_scopes.seconds_by_program(run.trace, plane, win)
        took.append(sum(by_program.get(name, 0.0) for name in names))
    if not took or not sum(took):
        return None
    return sum(took) / len(took) / passes
