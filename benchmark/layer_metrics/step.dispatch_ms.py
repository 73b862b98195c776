"""Layer ``step``: mean host time to dispatch one step, from the
program's ``trainer.step_dispatch_s`` over the window (its exact sum over
its exact count; its percentiles are quantised to quarter-octave
buckets).  Dispatch cost, not step time."""


def read(run):
    n = run.stats.get("trainer.step_dispatch_s.count")
    took = run.stats.get("trainer.step_dispatch_s.sum")
    return 1e3 * took / n if n else None
