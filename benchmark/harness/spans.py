"""The benchmark's own spans around the program's layer boundaries.

Each span is written twice: into a list on the host's ``perf_counter``
(the per-layer metrics of host work read that) and, through
``jax.profiler.TraceAnnotation``, into the profiler's trace when one is
running, where it shares a clock with the device's operations (the gap
attribution reads that).  Spans observe: a wrapped call gets the same
arguments and returns the same result.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import List, NamedTuple

import jax

PREFIX = "bench."


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    thread: str


class SpanLog:
    def __init__(self):
        self.records: List[Span] = []       # list.append is atomic

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append(Span(name, t0, time.perf_counter(),
                                         threading.current_thread().name))

    def wrap(self, name: str, fn):
        @functools.wraps(fn)
        def observed(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return observed

    def named(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> List[Span]:
        """Spans called ``name`` that ended inside [t0, t1]."""
        return [s for s in list(self.records)
                if s.name == name and t0 <= s.t1 <= t1]
