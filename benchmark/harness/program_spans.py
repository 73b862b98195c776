"""The program's own spans (``paddlebox_tpu.utils.trace.span``), read
where the benchmark finds them: as ``<name>_s`` histograms in the window's
stat deltas, and as ``pbx:<name>`` events on ``/host:CPU`` of the
profiler's trace, on the clock of the device's operations.

``xplane.load`` keeps only the benchmark's ``bench.*`` host events, so the
host plane is read again here, keeping ``bench.window`` and ``pbx:*``, and
handed to ``xplane.attribute_gaps`` as it is.  Three things are decided
before that:

* The profiler names a host line after the OS thread (every Python thread
  is ``python3``, and a thread id is reused by the next short-lived
  thread), so a line is not a Python thread.  There are two lines here:
  ``main``, the one that holds ``bench.window`` (it dispatches), and
  ``workers``, every other line merged.  ``attribute_gaps`` gives an
  instant to the innermost (shortest) span open on a line, main first, so
  a gap in which main holds no span goes to the innermost span open on
  ANY other thread: the build thread's pull before the prefetch worker's
  ``data.prefetch.build`` around it.
* A wait is transparent (``WAITS`` are dropped): the gap then falls to the
  span open on the thread being waited for.
* ``pbx:data.read.*`` run on several reader threads at once, so no instant
  under them has one owner and shortest-first would hand the gap to
  whichever chunk span happens to be shortest.  They are dropped too: the
  gap stays with ``pbx:data.load_into_memory`` around them, and the
  split of that lump is the thread-second metrics (``per_pass``).
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.harness import xplane

PREFIX = "pbx:"
WAITS = ("pbx:data.prefetch.wait", "pbx:ps.engine.wait_build")
CONCURRENT = "pbx:data.read."
MAIN, WORKERS = "main", "workers"
UNATTRIBUTED = "unattributed"


def per_pass(run, span: str) -> Optional[float]:
    """Seconds a pass spent under the program's span ``span``, summed over
    the threads that ran it, from the window's delta of ``<span>_s.sum``.
    None where the program has no such span (the parent of the PR that
    added it)."""
    passes = run.stats.get("data.prefetch.passes") or len(run.units)
    took = run.stats.get(span + "_s.sum")
    return took / passes if took and passes else None


def host_lines(path: str) -> Dict[str, list]:
    """``{"main": [...], "workers": [...]}``: the window span and the
    program's spans that own idle time, from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    lines: Dict[str, list] = {MAIN: [], WORKERS: []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            kept = [xplane.Event(ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events
                    if ev.name == xplane.WINDOW_SPAN
                    or ev.name.startswith(PREFIX)]
            dispatches = any(e.name == xplane.WINDOW_SPAN for e in kept)
            lines[MAIN if dispatches else WORKERS] += kept
    return owners(lines)


def owners(lines: Dict[str, list]) -> Dict[str, list]:
    """The lines without the spans that own no idle time."""
    return {line: [e for e in events if e.name not in WAITS
                   and not e.name.startswith(CONCURRENT)]
            for line, events in lines.items()}


def idle_by_span(device_trace: xplane.Trace, lines: Dict[str, list],
                 win: xplane.Interval) -> Dict[str, float]:
    """Idle seconds of the first chip by the program's span the host was
    in (``unattributed`` where it was in none), as
    ``xplane.attribute_gaps`` attributes them."""
    planes = xplane.device_planes(device_trace)
    if not planes:
        return {}
    trace = {planes[0]: device_trace[planes[0]], xplane.HOST_PLANE: lines}
    return xplane.attribute_gaps(trace, planes[0], win)


def attributed_share(by_span: Dict[str, float]) -> Optional[float]:
    """Per cent of the idle seconds that lie under some span."""
    idle = sum(by_span.values())
    if not idle:
        return None
    return 100.0 * (1.0 - by_span.get(UNATTRIBUTED, 0.0) / idle)
