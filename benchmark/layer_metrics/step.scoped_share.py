"""Layer ``step``: per cent of the step's device time that lies under
some name of the program's ``DEVICE_SCOPES`` or of a ``tower.*`` /
``seq.*`` scope: how much of the step the scopes own, as
``device.idle_attributed_share`` says it of the idle time
(``harness/step_scopes.py``)."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.scoped_share(run)
