#!/usr/bin/env python3
"""Idle seconds of the chip by the program's span the host was in.

    python3 benchmark/tools/idle_by_span.py <file.xplane.pb>

What the ledger's ``breakdown.idle_gaps`` gives by the benchmark's
``bench.*`` spans, by the program's own ``pbx:*`` spans
(``paddlebox_tpu.utils.trace.span``), for any trace of the program: the
benchmark's (``--trace 1 --keep``), ``utils/profiler.Profiler``'s, an
operator's.  The window is the ``bench.window`` span where the trace has
one, else from the first device operation to the last.  Rules of the
attribution: ``harness/program_spans.py``.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import program_spans, xplane           # noqa: E402


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    trace = xplane.load(argv[1])
    planes = xplane.device_planes(trace)
    if not planes:
        print("no /device:TPU:<n> plane in this trace: nothing ran on a "
              "chip while it was taken", file=sys.stderr)
        return 1
    win = xplane.window(trace)
    if win is None:
        ops = trace[planes[0]].get(xplane.OPS_LINE, [])
        if not ops:
            print("no device operation in this trace", file=sys.stderr)
            return 1
        win = (min(e.start for e in ops), max(e.end for e in ops))
    by_span = program_spans.idle_by_span(
        trace, program_spans.host_lines(argv[1]), win)
    window_s = (win[1] - win[0]) / 1e9
    idle_s = sum(by_span.values())
    print(f"window {window_s:.3f} s, {planes[0]} idle {idle_s:.3f} s "
          f"({100.0 * idle_s / window_s:.1f}%)")
    if not idle_s:
        return 0
    for name, seconds in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"{seconds:10.3f} s  {100.0 * seconds / idle_s:5.1f}%  {name}")
    print(f"under some span of the program: "
          f"{program_spans.attributed_share(by_span):.1f}% of the idle")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
