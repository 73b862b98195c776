"""What a generator measures and what a metric reader is handed."""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import time
from typing import Dict, List, Optional

from benchmark.harness import xplane
from benchmark.harness.compile_log import CompileLog
from benchmark.harness.spans import SpanLog


@dataclasses.dataclass
class Unit:
    """One pass (or one epoch over the resident pass) trained whole
    inside the window."""
    t0: float                  # its filelist handed to the loop / epoch start
    t1: float                  # its end_pass returned / epoch's last readback
    examples: int
    steps: int
    losses: List[float]
    auc: float


@dataclasses.dataclass
class Measured:
    units: List[Unit] = dataclasses.field(default_factory=list)
    t0: float = 0.0            # the window, on perf_counter
    t1: float = 0.0
    attempted: int = 0         # passes (stream) or steps (epochs)
    failed: int = 0
    checks: Dict[str, dict] = dataclasses.field(default_factory=dict)
    geometry: dict = dataclasses.field(default_factory=dict)
    lowering: str = ""
    data_stats: dict = dataclasses.field(default_factory=dict)
    stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace_file: Optional[str] = None


class Context:
    """Everything a generator needs from the harness."""

    def __init__(self, cell, cfg: dict, args, devices, work_dir: str,
                 t_start: float):
        self.cell, self.cfg, self.args = cell, cfg, args
        self.devices = devices
        self.work_dir = work_dir
        self.t_start = t_start
        self.spans = SpanLog()
        self.compile_log = CompileLog()
        self.rehearse = bool(args.rehearse)
        self.trace = bool(args.trace)
        self.setup_s: Optional[float] = None

    def traffic(self, key: str):
        """A traffic parameter; under ``--rehearse`` the mix's
        ``rehearsal`` block wins where it has the key."""
        t = self.cell.traffic
        if self.rehearse and key in t.get("rehearsal", {}):
            return t["rehearsal"][key]
        return t[key]

    def pair(self, key: str):
        """A parameter of this cell's own (``cells/<cell>.json``)."""
        return self.cell.param(key, self.rehearse)

    def program(self):
        from benchmark.harness.program import Program
        return Program(self.cell, self.cfg, self.args.seed, self.spans,
                       self.devices)

    def window_seconds(self) -> float:
        """How long the window lasts: ``--seconds``, or the mix's short
        traced window under ``--trace 1``."""
        if self.trace:
            return min(float(self.traffic("trace_seconds")),
                       float(self.args.seconds))
        return float(self.args.seconds)

    @contextlib.contextmanager
    def window(self, measured: Measured):
        """The measured window.  Set-up ends where it starts; the
        program's counters are read at both ends; under ``--trace 1`` the
        profiler runs around it and the window is a span in its trace."""
        from paddlebox_tpu.utils.monitor import stat_snapshot
        import jax
        self.setup_s = time.perf_counter() - self.t_start
        trace_dir = os.path.join(self.work_dir, "trace")
        if self.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # spans, not every call
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        before = stat_snapshot()
        try:
            with self.spans.span("window"):
                measured.t0 = time.perf_counter()
                yield
                measured.t1 = time.perf_counter()
        finally:
            after = stat_snapshot()
            if self.trace:
                jax.profiler.stop_trace()
                found = glob.glob(os.path.join(
                    trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
                measured.trace_file = max(found, key=os.path.getmtime) \
                    if found else None
        measured.stats = {k: after[k] - before.get(k, 0.0) for k in after
                          if not k.endswith((".p50", ".p95", ".p99", ".max"))}


class Run:
    """One finished run, as the metric readers see it.  A reader takes
    what it needs and returns a number, or None where there is nothing to
    read (the harness then leaves the metric out)."""

    def __init__(self, ctx: Context, measured: Measured,
                 peaks: Optional[dict]):
        self.cell, self.cfg, self.chips = ctx.cell, ctx.cfg, ctx.cell.chips
        self.measured = measured
        self.setup_s = ctx.setup_s
        self.peaks = peaks
        self.device: dict = {}     # filled in once the trace is reduced
        self.timed = not ctx.rehearse          # off-chip: no timing metric
        self.spans = ctx.spans
        self.compiles = ctx.compile_log.between(measured.t0, measured.t1)
        self.stats = measured.stats
        self.geometry = measured.geometry
        self._trace = None

    # -- the window ----------------------------------------------------------
    @property
    def elapsed_s(self) -> float:
        return self.measured.t1 - self.measured.t0

    @property
    def units(self) -> List[Unit]:
        return self.measured.units

    @property
    def steps(self) -> int:
        return sum(u.steps for u in self.units)

    def span_seconds(self, name: str) -> List[float]:
        """Durations of the benchmark's spans called ``name`` that ended
        inside the window."""
        return [s.t1 - s.t0 for s in
                self.spans.named(name, self.measured.t0, self.measured.t1)]

    # -- the trace -----------------------------------------------------------
    @property
    def trace(self) -> Optional[xplane.Trace]:
        if self._trace is None and self.measured.trace_file:
            self._trace = xplane.load(self.measured.trace_file)
        return self._trace

    @property
    def trace_window(self):
        return xplane.window(self.trace) if self.trace else None

    def step_runs(self, plane: str):
        """Device runs of the jitted train step inside the traced
        window."""
        return xplane.module_runs(self.trace, plane, self.trace_window,
                                  "jit_step")
