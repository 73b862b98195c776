"""Layer ``ps_engine``: seconds of ``end_pass`` (working set to host,
write-back to the table), mean over the window's passes.  The benchmark's
span, host clock."""

import statistics


def read(run):
    spans = run.span_seconds("end_pass")
    return statistics.fmean(spans) if spans else None
