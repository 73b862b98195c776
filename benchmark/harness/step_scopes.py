"""Device time of a step by the names the program gave its parts
(``paddlebox_tpu.utils.trace.DEVICE_SCOPES``), and of the window by the
compiled program that ran (``DEVICE_PROGRAMS``).

The profiler names a device operation by its HLO instruction, and the
trace itself says what that instruction is part of: its ``/host:metadata``
plane holds, for every program that ran while it was taken, the optimized
module as an ``Hlo Proto`` stat (PR 36 looked in the raw ``XSpace``:
``jax.profiler.ProfileData`` shows an event's own stats only).  An
instruction's ``metadata.op_name`` there is the path of the scopes it was
traced under, ``jit(step)/seq.pull/ps.pull.table/...``, and it is the
executable's own: a step that came out of a compile cache another tree
filled carries that tree's names, and a reader then finds no scope of its
own and says so (None), never a guess from instruction names.

``scopes`` reads the map with nothing but the protobuf wire format (five
message types, the fields below); the reduction is ``scope_share.py``'s:
a scope is a path element of ``op_name``, bare or inside a transform's
brackets; the instructions' intervals are united (a ``while`` and its
body count once) and clipped to the ``jit_step`` runs of the program the
map belongs to; ``ms_per_step`` divides by the runs.  None without a
trace, without the map, or without the scope.
"""

from __future__ import annotations

import bisect
import functools
import re
import sys
import time
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from benchmark.harness import xplane

METADATA_PLANE = "/host:metadata"
STEP = "jit_step"
# further names under which the program's models scope their towers and
# a row model's core its pull and push
SCOPE_FAMILIES = ("tower.", "seq.")


# -- the wire format ----------------------------------------------------------

def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a view
    of the bytes for a length-delimited field; fixed-width fields are
    passed over."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
            yield key >> 3, value
        elif kind == 2:
            size, at = _varint(buf, at)
            yield key >> 3, buf[at:at + size]
            at += size
        elif kind == 1:
            at += 8
        elif kind == 5:
            at += 4
        else:
            raise ValueError(f"wire type {kind} in a trace")


def _first(buf, number: int):
    return next((v for n, v in _fields(buf) if n == number), None)


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace") if view is not None else ""


# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (a map entry:
# value = 2); XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6;
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
# .metadata = 7; OpMetadata.op_name = 2
def programs(xspace) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` for the programs of a
    serialized ``XSpace`` whose module the trace carries (``jit_step(7)``:
    the name a run has on ``XLA Modules``).  An instruction without an
    ``op_name`` is left out."""
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        for number, entry in _fields(plane):
            if number != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            for number, stat in _fields(meta):
                proto = _first(stat, 6) if number == 5 else None
                if proto is not None:
                    out[_text(_first(meta, 2))] = _instructions(proto)
    return out


def _instructions(hlo_proto) -> Dict[str, str]:
    out = {}
    module = _first(hlo_proto, 1)
    for number, computation in _fields(module) if module is not None else ():
        if number != 3:
            continue
        for number, instruction in _fields(computation):
            if number != 2:
                continue
            meta = _first(instruction, 7)
            op = _text(_first(meta, 2)) if meta is not None else ""
            if op:
                out[_text(_first(instruction, 1))] = op
    return out


@functools.lru_cache(maxsize=2)
def _programs_of(path: str) -> Dict[str, Dict[str, str]]:
    t0 = time.perf_counter()
    with open(path, "rb") as f:
        raw = f.read()
    found = programs(raw)
    # what the scopes' readers cost a traced run, after its window
    print(f"[benchmark] step_scopes: the modules of {len(found)} programs "
          f"read from a {len(raw) / 1e6:.1f} MB trace in "
          f"{time.perf_counter() - t0:.2f} s", file=sys.stderr, flush=True)
    return found


def scopes(run) -> Dict[str, Dict[str, str]]:
    """The maps of the step programs in a run's trace, by program name;
    empty without a trace or where the trace carries no module."""
    path = getattr(run.measured, "trace_file", None)
    if not path:
        return {}
    return {name: ops for name, ops in _programs_of(path).items()
            if STEP in name}


# -- the reduction ------------------------------------------------------------

def element(scope: str) -> "re.Pattern":
    """``scope`` as a path element of an ``op_name``, a whole one or the
    head of one (``tower.`` for every ``tower.*``): .../seq.pull/...,
    .../jvp(tower.kda)/..."""
    tail = "" if scope.endswith(".") else r"([/)]|$)"
    return re.compile(r"(^|[/(])" + re.escape(scope) + tail)


def instructions_under(ops: Dict[str, str], names: Iterable[str]) -> set:
    patterns = [element(n) for n in names]
    return {instruction for instruction, op in ops.items()
            if any(p.search(op) for p in patterns)}


def _inside(intervals, holes) -> float:
    """Nanoseconds of ``intervals`` that lie inside ``holes`` (both
    united first; one sweep each)."""
    intervals = xplane.union(intervals)
    return xplane.total(intervals) - xplane.total(
        xplane.subtract(intervals, holes))


def _runs_by_program(run):
    """(instruction -> op_name, the window's operations, the runs) of each
    step program that ran, on each of the cell's chips: a run is read by
    the map of its own program."""
    by_program = scopes(run)
    win = run.trace_window
    if not by_program or run.trace is None or win is None:
        return
    for plane in xplane.device_planes(run.trace)[:run.chips]:
        ops = xplane.ops(run.trace, plane, win)
        by_name: Dict[str, List[xplane.Event]] = {}
        for e in run.step_runs(plane):
            by_name.setdefault(e.name, []).append(e)
        for program, events in by_name.items():
            # a trace with one step program names it however the runs do
            table = by_program.get(program) or (
                next(iter(by_program.values()))
                if len(by_program) == 1 else None)
            if table is not None:
                yield table, ops, events


def step_seconds(run, names: Sequence[str]) -> Optional[Tuple[float, float,
                                                               int]]:
    """(seconds under any of the scopes ``names`` inside the step's runs,
    seconds of those runs, the runs) over the cell's chips.  None where
    no instruction of a program that ran lies under one of the names."""
    under_ns = step_ns = runs = 0
    found = False
    for table, ops, events in _runs_by_program(run):
        wanted = instructions_under(table, names)
        found = found or bool(wanted)
        holes = [(e.start, e.end) for e in events]
        under_ns += _inside(((e.start, e.end) for e in ops
                             if e.name in wanted), holes)
        step_ns += xplane.total(holes)
        runs += len(events)
    if not found or not runs:
        return None
    return under_ns / 1e9, step_ns / 1e9, runs


def ms_per_step(run, names: Sequence[str]) -> Optional[float]:
    """Device milliseconds a step under the scopes ``names``, united."""
    got = step_seconds(run, names)
    return None if got is None else 1e3 * got[0] / got[2]


def table_names() -> Optional[Tuple[str, ...]]:
    """The program's closed list of device scope names; None where the
    program has none (the parent of the PR that added it)."""
    from paddlebox_tpu.utils import trace
    return getattr(trace, "DEVICE_SCOPES", None)


def scoped_share(run) -> Optional[float]:
    """Per cent of the step's device time under any name of the program's
    table or of a scope family: the coverage of the scopes."""
    names = table_names()
    if names is None:
        return None
    got = step_seconds(run, tuple(names) + SCOPE_FAMILIES)
    return None if got is None else 100.0 * got[0] / got[1]


def longest(run, names: Optional[Sequence[str]] = None, n: int = 10
            ) -> List[Tuple[str, str, float]]:
    """The step's instructions under the scopes ``names`` by device time:
    (instruction as the profiler wrote it, its op_name, ms a step), for
    ``tools/device_by_program.py``; a ``while`` shows with the whole
    loop's time, its body's instructions beside it.  ``names`` None: the
    instructions under no name of the table or a family, each with the
    part of its time that no owned instruction covers (a loop outside
    the scopes whose body is inside them: what the loop itself costs)."""
    took: Dict[Tuple[str, str], float] = {}
    runs = 0
    for table, ops, events in _runs_by_program(run):
        owned = instructions_under(
            table, names if names is not None
            else tuple(table_names() or ()) + SCOPE_FAMILIES)
        holes = sorted((e.start, e.end) for e in events)
        starts = [a for a, _ in holes]
        runs += len(events)
        covered = _Covered((e.start, e.end) for e in ops if e.name in owned)
        for e in ops:
            # an operation lies in one run: the last that began before it
            k = bisect.bisect_right(starts, e.start) - 1
            if k < 0 or e.start >= holes[k][1] \
                    or (e.name in owned) == (names is None):
                continue
            a, b = e.start, min(e.end, holes[k][1])
            key = (e.text, table.get(e.name, ""))
            took[key] = took.get(key, 0.0) + (b - a) - (
                covered.between(a, b) if names is None else 0.0)
    top = sorted(took.items(), key=lambda kv: -kv[1])[:n]
    return [(text, op, ns / 1e6 / runs) for (text, op), ns in top if ns]


class _Covered:
    """Length of a set of intervals inside any [a, b], by bisection."""

    def __init__(self, intervals):
        self.spans = xplane.union(intervals)
        self.starts = [a for a, _ in self.spans]
        self.before = [0.0]
        for a, b in self.spans:
            self.before.append(self.before[-1] + b - a)

    def upto(self, x: float) -> float:
        k = bisect.bisect_right(self.starts, x)
        if not k:
            return 0.0
        a, b = self.spans[k - 1]
        return self.before[k - 1] + min(x, b) - a

    def between(self, a: float, b: float) -> float:
        return self.upto(b) - self.upto(a)


# -- whole programs -----------------------------------------------------------

def program_names() -> Optional[Tuple[str, ...]]:
    """The names of the feed's device programs; None where the program
    does not export them."""
    from paddlebox_tpu.utils import trace
    return getattr(trace, "DEVICE_PROGRAMS", None)


def seconds_by_program(trace: xplane.Trace, plane: str,
                       win: xplane.Interval) -> Dict[str, float]:
    """Device seconds inside the window by program (``jit_step``: the
    name without the run's id); a run across the window's edge counts
    for its part inside."""
    out: Dict[str, float] = {}
    for e in trace[plane].get(xplane.MODULES_LINE, []):
        a, b = max(e.start, win[0]), min(e.end, win[1])
        if b > a:
            name = e.name.split("(", 1)[0]
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out
