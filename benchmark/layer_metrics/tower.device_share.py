"""Layer ``tower`` (models/looplm.py): the share of the step's device
time spent in instructions under the model's ``tower.*`` named scopes
(the recurrent layers, the head and the loss, forward and backward).

The trace names a device operation by its HLO instruction and carries no
scope (PR 28 looked: an event's stats are its offset and duration), so an
instruction's scope comes from the compiled step's text, which the
generator keeps as instruction name -> ``op_name`` (``measured.scopes``);
a program without such scopes leaves the map empty and the metric out.
Intervals are united, so a ``while`` and the instructions of its body
count once."""

from benchmark.harness import xplane


def read(run):
    scopes = getattr(run.measured, "scopes", None)
    win = run.trace_window
    if not scopes or run.trace is None or win is None:
        return None
    tower, step = 0.0, 0.0
    for plane in xplane.device_planes(run.trace)[:run.chips]:
        inside = xplane.union(
            (e.start, e.end) for e in xplane.ops(run.trace, plane, win)
            if e.name in scopes)
        for e in run.step_runs(plane):
            tower += xplane.total(xplane.clip(inside, e.start, e.end))
            step += e.end - e.start
    if step <= 0 or tower <= 0:
        return None
    return 100.0 * tower / step
