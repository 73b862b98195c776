"""Layer ``step`` (``ps/mxu_path.py::_push_sorted``): device milliseconds
a step under the ``ps.push.cross`` scope, the gradient's way from the
canonical order to the scatter kernel's sorted payload
(``harness/step_scopes.py``)."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_per_step(run, ("ps.push.cross",))
