"""Host spans recorded by the harness round its calls into the program.

Each span is kept in memory as ``(name, start_s, end_s)`` on
``time.perf_counter`` and, while a profiler trace is being taken, also written
into the profiler's own trace as a ``jax.profiler.TraceAnnotation`` so that it
sits on the device trace's clock."""

from __future__ import annotations

import contextlib
import time
from typing import List, Tuple


class Spans:
    def __init__(self) -> None:
        self.rows: List[Tuple[str, float, float]] = []
        self.annotate = False  # set while a profiler trace is open
        self.recording = False  # set while the measured window is open

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.recording:
            yield
            return
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.rows if n == name]
