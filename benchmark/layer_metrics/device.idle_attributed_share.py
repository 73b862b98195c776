"""Layer ``device``: the share of the chip's idle time in the traced
window that lies under some span of the program (``pbx:*`` on
``/host:CPU``): the check that the program's spans share the device's
clock and cover the loop.  Waits are transparent and the per-chunk reader
spans own nothing (``harness/program_spans.py``).  None where the trace
holds no span of the program."""

from benchmark.harness import program_spans


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    lines = program_spans.host_lines(run.measured.trace_file)
    if not any(e.name.startswith(program_spans.PREFIX)
               for events in lines.values() for e in events):
        return None
    return program_spans.attributed_share(program_spans.idle_by_span(
        run.trace, lines, run.trace_window))
