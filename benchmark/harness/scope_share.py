"""The share of the step's device time spent in instructions under one
named scope of the model, as ``layer_metrics/tower.device_share.py``
reads ``tower.*``: the generator keeps instruction name -> ``op_name`` of
the compiled step (``measured.scopes``; the trace carries no scope), the
scope's instructions' intervals are united and clipped to each
``jit_step`` run.  A program without the scope, a run without a trace or
without the map: None, and the metric is left out."""

import re

from benchmark.harness import xplane


def read(run, scope: str):
    scopes = getattr(run.measured, "scopes", None)
    win = run.trace_window
    if not scopes or run.trace is None or win is None:
        return None
    # a scope is a path element of ``op_name``, bare or inside a
    # transform's brackets: .../tower.kda/..., .../jvp(tower.kda)/...
    inside_scope = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    names = {name for name, op in scopes.items() if inside_scope.search(op)}
    if not names:
        return None
    inside_s, step = 0.0, 0.0
    for plane in xplane.device_planes(run.trace)[:run.chips]:
        inside = xplane.union(
            (e.start, e.end) for e in xplane.ops(run.trace, plane, win)
            if e.name in names)
        for e in run.step_runs(plane):
            inside_s += xplane.total(xplane.clip(inside, e.start, e.end))
            step += e.end - e.start
    if step <= 0 or inside_s <= 0:
        return None
    return 100.0 * inside_s / step
