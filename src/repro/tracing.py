"""Guard's own spans and counters: the program's one tracing facility.

``span(name)`` wraps a phase of Guard's work (the detector's ring push,
the scorer's round trip, the trainer's dispatch, a snapshot's write...)
and ``count(name, n)`` adds to a counter. Every name is declared once,
in ``SPAN_NAMES`` and ``COUNTER_NAMES``.

Where the record goes:

* **Off** (the default): a span only opens a ``jax.profiler``
  ``TraceAnnotation``, and only when ``jax`` is already imported (this
  module never imports it, so the numpy-only modules stay so). An
  operator's own profile of a job then shows Guard's phases on the same
  clock as the device's operations. ``count`` does nothing.
* **Attached**: ``attach(recorder)`` sends every span through
  ``recorder.span(name)`` (any object whose ``span(name)`` is a context
  manager, such as ``Recorder``) and keeps ``counters``, cleared by
  ``attach``.
* **Profiled**: while a ``jax.profiler`` trace runs and no recorder is
  attached, the spans and counters go to ``profiled``, a ``Recorder``
  begun afresh (with ``counters`` cleared) by the first span or count
  that finds a trace running where the one before found none. Whoever
  took the trace reads there what Guard did during it. Two traces with
  no span or count between them share one record.

While recording either way, Python's garbage collections are a span
(``python.gc``) too.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time
from typing import Dict, List, Optional

SPAN_NAMES = (
    # core/detector.py, kernels/fleet_score/ops.py, core/policy.py
    "detector.push", "detector.score", "detector.verdicts",
    "fleet_score.call", "fleet_score.dispatch", "fleet_score.fetch",
    "policy.tiers",
    # guard/hook.py, guard/session.py
    "guard.hook", "guard.observe",
    # train/trainer.py, train/checkpoint.py
    "train.batch", "train.dispatch", "train.wait", "train.metrics",
    "train.snapshot", "train.hook", "train.restore",
    "ckpt.snapshot.copy", "ckpt.snapshot.write",
    # diagnose/rootcause.py
    "diagnose.attribute",
    # this module, while a recorder is attached or a trace runs
    "python.gc",
)
COUNTER_NAMES = (
    "detector.rows_scored", "fleet_score.bytes_in", "fleet_score.bytes_out",
    "guard.windows_scored", "ckpt.snapshot_bytes", "python.gc_collections",
)

_NOTHING = contextlib.nullcontext()


class Recorder:
    """Running totals of every span, by name: ``stats[name]`` is
    [seconds, calls, longest call's seconds], so the record does not
    grow with the run. Each span is also a ``TraceAnnotation`` when
    ``jax`` is imported."""

    def __init__(self) -> None:
        self.stats: Dict[str, List[float]] = {}

    def span(self, name: str) -> "_Timed":
        return _Timed(self, name)

    def add(self, name: str, seconds: float) -> None:
        s = self.stats.get(name)
        if s is None:
            self.stats[name] = [seconds, 1, seconds]
        else:
            s[0] += seconds
            s[1] += 1
            s[2] = max(s[2], seconds)

    def total(self, name: str) -> float:
        return self.stats[name][0] if name in self.stats else 0.0

    def calls(self, name: str) -> int:
        return int(self.stats[name][1]) if name in self.stats else 0


class _Timed:
    """One span of a ``Recorder``: the annotation around the timing."""
    __slots__ = ("rec", "name", "ann", "t0")

    def __init__(self, rec: Recorder, name: str) -> None:
        self.rec, self.name = rec, name

    def __enter__(self) -> "_Timed":
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.rec.add(self.name, dt)


counters: Dict[str, int] = {}
profiled: Optional[Recorder] = None
_attached = None
_in_trace = False        # the last look found a trace running
_gc_span = None          # the open python.gc span, between its callbacks


def _annotation(name: str):
    prof = sys.modules.get("jax.profiler")
    return prof.TraceAnnotation(name) if prof is not None else _NOTHING


def _tracing() -> bool:
    prof = sys.modules.get("jax.profiler")
    return prof is not None and prof.TraceAnnotation.is_enabled()


def _recorder():
    """The recorder in force: the attached one, else ``profiled`` while
    a trace runs, else None."""
    global _in_trace, profiled
    if _attached is not None:
        return _attached
    now = _tracing()
    if now != _in_trace:
        _in_trace = now
        if now:
            profiled = Recorder()
            counters.clear()
        _watch_gc(now)
    return profiled if now else None


def span(name: str):
    """Context manager around one phase of Guard's work."""
    rec = _recorder()
    return rec.span(name) if rec is not None else _annotation(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to a counter while a recorder is attached or a trace
    runs."""
    if _recorder() is not None:
        counters[name] = counters.get(name, 0) + int(n)


def attach(recorder) -> None:
    """Record every span in ``recorder`` and count from zero."""
    global _attached
    _attached = recorder
    counters.clear()
    _watch_gc(True)


def detach() -> None:
    """Back to the default: annotations only, or ``profiled`` (the same
    record as before the attach) while a trace runs."""
    global _attached
    _attached = None
    _watch_gc(_in_trace)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        # no _recorder(): that may edit gc.callbacks, which run now
        rec = _attached if _attached is not None else \
            profiled if _in_trace and _tracing() else None
        if rec is not None and _gc_span is None:
            counters["python.gc_collections"] = \
                counters.get("python.gc_collections", 0) + 1
            _gc_span = rec.span("python.gc")
            _gc_span.__enter__()
    elif _gc_span is not None:
        cm, _gc_span = _gc_span, None
        cm.__exit__(None, None, None)


def _watch_gc(on: bool) -> None:
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


__all__ = ["COUNTER_NAMES", "Recorder", "SPAN_NAMES", "attach", "count",
           "counters", "detach", "span"]
