#!/usr/bin/env python3
"""Busy seconds of the chip by the compiled program that ran, and a
step's milliseconds by the program's own scope names.

    python3 benchmark/tools/device_by_program.py <file.xplane.pb>

What ``device.step_share`` and ``feed_build.plans_device_s_per_pass`` sum,
program by program (``jit_step``, the feed's ``jit__build_plans`` ...,
the working set's upload and write-back), and under it the parts of a
``jit_step`` run by scope (``paddlebox_tpu.utils.trace.DEVICE_SCOPES``),
for any trace of the program (the benchmark's with ``--trace 1 --keep``).
The window is the ``bench.window`` span where the trace has one, else
from the first device operation to the last.  The reduction:
``harness/step_scopes.py``.
"""

from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import step_scopes, xplane             # noqa: E402


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
    busy = xplane.busy_seconds(trace, win)[0]
    print(f"window {(win[1] - win[0]) / 1e9:.3f} s, {planes[0]} busy "
          f"{busy:.3f} s")
    by_program = step_scopes.seconds_by_program(trace, planes[0], win)
    for name, seconds in sorted(by_program.items(), key=lambda kv: -kv[1]):
        print(f"{seconds:10.3f} s  {100.0 * seconds / busy:5.1f}%  {name}")
    print(f"{sum(by_program.values()):10.3f} s  in runs of some program")
    run = types.SimpleNamespace(
        measured=types.SimpleNamespace(trace_file=argv[1]), trace=trace,
        trace_window=win, chips=1)
    run.step_runs = lambda plane: xplane.module_runs(
        trace, plane, win, step_scopes.STEP)
    names = step_scopes.table_names() or ()
    rows = [(n, step_scopes.ms_per_step(run, (n,)))
            for n in names + step_scopes.SCOPE_FAMILIES]
    if any(ms is not None for _, ms in rows):
        print("a jit_step run, ms under each scope (they nest: "
              "seq.* hold ps.*):")
        for name, ms in rows:
            if ms is not None:
                print(f"{ms:10.3f} ms  {name}")
                for text, _, part in step_scopes.longest(run, (name,), 3):
                    print(f"{'':14}{part:8.3f}  {text[:100]}")
        print(f"{step_scopes.scoped_share(run):10.2f} %   of the step "
              "under some scope; the longest instructions under none:")
        for text, op, ms in step_scopes.longest(run):
            print(f"{ms:10.3f} ms  {text[:110]}  [{op or 'no op_name'}]")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
