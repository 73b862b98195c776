"""Layer ``step`` (``ps/mxu_path.py::pull_rows`` / ``pull_pool_cvm``):
device milliseconds a step under ``ps.pull.cross`` (the sorted ->
canonical crossing: relayout, takes) and ``ps.pull.pool`` (the sum over a
slot's capacity and the CVM transform), united: the compiler fuses the
pooling sum into the crossing's gathers where it can
(``harness/step_scopes.py``)."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_per_step(run, ("ps.pull.cross", "ps.pull.pool"))
