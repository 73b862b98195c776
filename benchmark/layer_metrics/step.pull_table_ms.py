"""Layer ``step`` (``ps/mxu_path.py::_pull_table``): device milliseconds
a step under the ``ps.pull.table`` scope, the build of the feature-major
pull table from the working set (``harness/step_scopes.py``)."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_per_step(run, ("ps.pull.table",))
