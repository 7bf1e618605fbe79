"""The program's own spans and counters over a traced window.

While a profiler trace runs and no recorder is attached, ``repro.tracing``
keeps Guard's span totals (``tracing.profiled``) and counters
(``tracing.counters``) for that trace; the traced window is such a trace.
A program without ``repro.tracing`` has nothing to read.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence


def totals(spans: Sequence[str] = (), counters: Sequence[str] = ()
           ) -> Optional[Dict[str, float]]:
    """Seconds of each span and the value of each counter over the last
    trace, by name; None unless the program recorded every one of them."""
    try:
        from repro import tracing
    except ImportError:
        return None
    rec = getattr(tracing, "profiled", None)
    if rec is None or any(s not in rec.stats for s in spans) or \
            any(c not in tracing.counters for c in counters):
        return None
    out = {s: rec.total(s) for s in spans}
    out.update({c: float(tracing.counters[c]) for c in counters})
    return out
