"""Layer ``step`` (``trainer._pooled_dense_half``): device milliseconds a
step under the ``dense.tower`` scope, the pooled model's forward and
backward (the row models' towers are ``tower.device_share``'s)
(``harness/step_scopes.py``)."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_per_step(run, ("dense.tower",))
