"""Host spans recorded from the benchmark's own files.

A span wraps one call into a layer of the program: its host-clock
duration is kept in memory, and when a trace is being taken it is also
written into the profiler's trace as a ``TraceAnnotation`` of the same
name, so the trace reduction can say what the host was doing during each
idle gap of the device.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List


class Spans:
    def __init__(self) -> None:
        self.durations: Dict[str, List[float]] = defaultdict(list)
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.durations[name].append(time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    def total(self, name: str) -> float:
        return float(sum(self.durations.get(name, ())))
