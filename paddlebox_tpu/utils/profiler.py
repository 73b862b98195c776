"""Profiling hooks.

≙ the reference's two tracing layers (SURVEY.md §5): the new-style
host+device tracer exporting Chrome traces (platform/profiler/profiler.h,
python paddle.profiler.Profiler profiler.py:271 with scheduler states) and
the old RecordEvent spans (platform/profiler.cc) — mapped onto jax.profiler
(XLA's TraceMe/Perfetto machinery) plus the framework's TimerRegistry for
the per-pass wall-time report (≙ PrintSyncTimer box_wrapper.h:795).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from paddlebox_tpu.utils import trace
from paddlebox_tpu.utils.timer import TimerRegistry


# ≙ platform::RecordEvent span / paddle.profiler annotate: both are the
# program's one span primitive (a ``pbx:<name>`` annotation in the device
# trace, a ``<name>_s`` sample, a ring span when the host tracer is on)
RecordEvent = annotate = trace.span


class Profiler:
    """≙ paddle.profiler.Profiler (profiler.py:271): scheduler-driven
    start/stop with chrome-trace export.  States: CLOSED→RECORD→CLOSED by
    step range (the reference's ProfilerState scheduler, profiler.py:34)."""

    def __init__(self, log_dir: str = "./profile_out",
                 record_steps: Optional[range] = None):
        self.log_dir = log_dir
        self.record_steps = record_steps or range(2, 7)
        self._step = 0
        self._running = False

    def start(self) -> None:
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir)
        self._running = True

    def stop(self) -> None:
        if self._running:
            jax.profiler.stop_trace()
            self._running = False
            if trace.ACTIVE is not None:
                # merge the host span ring into the same trace collection:
                # host_spans.trace.json lands beside the XLA dump, so one
                # Perfetto load shows device ops AND PS verb spans
                trace.ACTIVE.export_chrome_trace(self.log_dir)

    def step(self) -> None:
        """Call once per train step; starts/stops per the schedule."""
        if self._step == self.record_steps.start:
            self.start()
        elif self._step == self.record_steps.stop:
            self.stop()
        self._step += 1

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
