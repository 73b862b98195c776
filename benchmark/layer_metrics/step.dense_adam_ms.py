"""Layer ``step`` (``trainer._pooled_dense_half`` / ``_rows_dense_half``):
device milliseconds a step under the ``dense.adam`` scope, the dense
optimizer's update of the parameters (``harness/step_scopes.py``)."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_per_step(run, ("dense.adam",))
