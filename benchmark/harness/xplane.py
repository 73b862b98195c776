"""From the profiler's trace to numbers: the reduction every PR shares.

``load`` turns an ``.xplane.pb`` into plain records with nothing but JAX
(``jax.profiler.ProfileData``); everything after works on those records,
so the arithmetic is checked on hand-built traces
(``benchmark/tests/test_xplane.py``).

A trace is ``{plane name: {line name: [Event]}}``.  On a TPU each chip is
a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event for
each operation the core ran and whose line ``XLA Modules`` one for each
run of a compiled program; host threads are lines of ``/host:CPU``, and
the benchmark's spans (``bench.*``) are events there.  All times are
nanoseconds on the profiler's one clock.

The profiler names a device operation by its whole HLO instruction,
``%sorted_spmm_gather.1 = f32[12,425984]{...} custom-call(...)``.  An
event's ``name`` is the instruction's own name (``sorted_spmm_gather.1``:
a Pallas kernel's ``name=`` and an XLA collective's kind are in it), and
matching is on that, because the rest of the text names the operands, so
every consumer of a kernel's result would match the kernel too.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")

Interval = Tuple[float, float]


class Event(NamedTuple):
    name: str             # a device op: its HLO instruction's name
    start: float          # ns
    end: float            # ns
    text: str             # as the profiler wrote it (the whole instruction)


Trace = Dict[str, Dict[str, List[Event]]]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace: Trace = {}
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue          # host: only the benchmark's spans
                events.append(Event(op_name(ev.name), ev.start_ns,
                                    ev.start_ns + ev.duration_ns, ev.name))
    return trace


def op_name(text: str) -> str:
    """``%fusion.5 = f32[...] fusion(...)`` -> ``fusion.5``."""
    return text.split(" = ", 1)[0].lstrip("%")


# -- interval arithmetic ----------------------------------------------------

def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Iterable[Interval], holes: Iterable[Interval]
             ) -> List[Interval]:
    """The parts of ``intervals`` that no hole covers (one sweep over the
    two sorted lists: a trace holds a hundred thousand operations)."""
    holes = union(holes)
    out, k = [], 0
    for a, b in union(intervals):
        while k < len(holes) and holes[k][1] <= a:
            k += 1
        j = k
        while j < len(holes) and holes[j][0] < b:
            if holes[j][0] > a:
                out.append((a, holes[j][0]))
            a = max(a, holes[j][1])
            j += 1
        if a < b:
            out.append((a, b))
    return out


# -- what the metrics ask ---------------------------------------------------

def device_planes(trace: Trace) -> List[str]:
    return sorted((p for p in trace if DEVICE_PLANE.match(p)),
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def window(trace: Trace) -> Optional[Interval]:
    """The measured window: the benchmark's ``bench.window`` span."""
    for events in trace.get(HOST_PLANE, {}).values():
        for ev in events:
            if ev.name == WINDOW_SPAN:
                return (ev.start, ev.end)
    return None


def ops(trace: Trace, plane: str, win: Interval) -> List[Event]:
    """The plane's operations that ran inside the window, clipped to it."""
    out = []
    for ev in trace[plane].get(OPS_LINE, []):
        a, b = max(ev.start, win[0]), min(ev.end, win[1])
        if b > a:
            out.append(ev._replace(start=a, end=b))
    return out


def busy_seconds(trace: Trace, win: Interval) -> List[float]:
    """For each chip, the seconds in which some operation ran."""
    return [total(union((e.start, e.end) for e in ops(trace, p, win))) / 1e9
            for p in device_planes(trace)]


def matching(events: Iterable[Event], needle: str) -> List[Event]:
    return [e for e in events if needle in e.name]


def module_runs(trace: Trace, plane: str, win: Interval, needle: str
                ) -> List[Event]:
    """Runs of the compiled program whose name holds ``needle`` that lie
    wholly inside the window."""
    return [e for e in trace[plane].get(MODULES_LINE, [])
            if needle in e.name and e.start >= win[0] and e.end <= win[1]]


def collective_spans(events: List[Event]) -> List[Interval]:
    """Intervals during which a collective is in flight.  A synchronous
    collective is its own event; an asynchronous one is a ``-start`` and a
    ``-done`` event, and is in flight from the first's start to the
    second's end (the k-th start pairs with the k-th done of its kind)."""
    spans: List[Interval] = []
    open_starts: Dict[str, List[Event]] = {}
    for ev in sorted(events, key=lambda e: e.start):
        kind = COLLECTIVE.search(ev.name)
        if not kind:
            continue
        if "-start" in ev.name:
            open_starts.setdefault(kind.group(1), []).append(ev)
        elif "-done" in ev.name:
            waiting = open_starts.get(kind.group(1))
            first = waiting.pop(0) if waiting else ev
            spans.append((first.start, ev.end))
        else:
            spans.append((ev.start, ev.end))
    return spans


def collective_seconds(trace: Trace, plane: str, win: Interval
                       ) -> Tuple[float, float]:
    """(seconds a collective was in flight, seconds of those in which no
    other operation ran on the chip)."""
    events = ops(trace, plane, win)
    spans = union(collective_spans(events))
    compute = [(e.start, e.end) for e in events
               if not COLLECTIVE.search(e.name)]
    return total(spans) / 1e9, total(subtract(spans, compute)) / 1e9


def idle_gaps(trace: Trace, plane: str, win: Interval) -> List[Interval]:
    return subtract([win], ((e.start, e.end) for e in ops(trace, plane, win)))


def host_spans(trace: Trace) -> Dict[str, List[Event]]:
    """The benchmark's spans by host thread, the window span left out."""
    return {line: [e for e in events if e.name != WINDOW_SPAN]
            for line, events in trace.get(HOST_PLANE, {}).items()
            if any(e.name != WINDOW_SPAN for e in events)}


def main_thread(trace: Trace) -> Optional[str]:
    for line, events in trace.get(HOST_PLANE, {}).items():
        if any(e.name == WINDOW_SPAN for e in events):
            return line
    return None


def attribute_gaps(trace: Trace, plane: str, win: Interval
                   ) -> Dict[str, float]:
    """Idle seconds of the chip by what the host was doing: each instant
    of a gap goes to the innermost span open on the dispatching thread
    (the one that holds ``bench.window``), else to the innermost span open
    on another thread (the prefetch worker the dispatcher waits for), else
    to ``unattributed``."""
    gaps = idle_gaps(trace, plane, win)
    by_thread = host_spans(trace)
    main = main_thread(trace)
    order = ([main] if main in by_thread else []) + sorted(
        t for t in by_thread if t != main)
    out: Dict[str, float] = {}
    left = gaps
    for thread in order:
        # innermost first: a shorter span that lies inside a longer one
        # takes its part of the gap before the longer one does
        for ev in sorted(by_thread[thread], key=lambda e: e.end - e.start):
            took = clip(left, ev.start, ev.end)
            if took:
                out[ev.name] = out.get(ev.name, 0.0) + total(took) / 1e9
                left = subtract(left, [(ev.start, ev.end)])
    if left:
        out["unattributed"] = total(left) / 1e9
    return out


def top_ops(trace: Trace, plane: str, win: Interval, n: int = 10,
            label: int = 160) -> List[Tuple[str, float]]:
    """The operations that took most device time.  Grouped by the whole
    instruction, since every program has a ``fusion.1`` of its own, and
    labelled with its start (name, result shape, kind)."""
    seconds: Dict[str, float] = {}
    for e in ops(trace, plane, win):
        seconds[e.text] = seconds.get(e.text, 0.0) + (e.end - e.start) / 1e9
    return [(k[:label], v) for k, v in
            sorted(seconds.items(), key=lambda kv: -kv[1])[:n]]
