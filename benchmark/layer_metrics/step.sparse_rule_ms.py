"""Layer ``step`` (``ps/mxu_path.py::push_and_update``): device
milliseconds a step under the ``ps.push.rule`` scope: the merged
accumulators out of the scatter's output and the whole-table sparse
optimizer (``ps/optimizer.apply_push``); a tied head's merge
(``seq.head_push``) lies inside it (``harness/step_scopes.py``)."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_per_step(run, ("ps.push.rule",))
