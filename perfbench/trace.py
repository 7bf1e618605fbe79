"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps attributed to what the host was doing.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain tuples; ``summarize`` works on those tuples alone, so the
reduction is tested on a small recorded trace without a chip.

Device events are those of the first accelerator plane's ``XLA Ops``
line. Host events are the benchmark's own ``TraceAnnotation`` spans on
the host plane. Both are on the profiler's clock. The traced window is
the span named ``WINDOW``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
Event = Tuple[str, int, int]           # (name, start_ns, end_ns)


def load_events(trace_dir: str, host_names: Iterable[str],
                raw_names: bool = False) -> Tuple[List[Event], List[Event]]:
    """(device ops, host spans) from the newest trace under ``trace_dir``;
    device ops by ``op_name`` unless ``raw_names``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    wanted = set(host_names) | {WINDOW}
    dev: List[Event] = []
    host: List[Event] = []
    dev_planes = sorted(p.name for p in pd.planes
                        if p.name.startswith("/device:TPU"))
    for plane in pd.planes:
        if dev_planes and plane.name == dev_planes[0]:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.extend((e.name if raw_names else op_name(e.name),
                                int(e.start_ns), int(e.end_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.name in wanted)
    return dev, host


def op_name(text: str) -> str:
    """Short name of a device op from its HLO text: the instruction's
    name, and for a custom call its target (a Mosaic kernel is a
    ``tpu_custom_call``)."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name} [{m.group(1)}]" if m else name


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def summarize(dev: Sequence[Event], host: Sequence[Event],
              kernels: Optional[Dict[str, str]] = None) -> Optional[dict]:
    """Busy and idle time of the device inside the traced window.

    Returns None when the window span is missing or no device operation
    ran in it. ``kernel_s[k]`` sums the device time of the operations
    whose name contains ``kernels[k]``.
    """
    win = [h for h in host if h[0] == WINDOW]
    if not win:
        return None
    w0, w1 = win[0][1], win[0][2]
    clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in dev
               if e > w0 and s < w1]
    if not clipped:
        return None
    busy = _merge([(s, e) for _, s, e in clipped])
    busy_ns = sum(e - s for s, e in busy)
    by_op: Dict[str, int] = defaultdict(int)
    for n, s, e in clipped:
        by_op[n] += e - s
    kernels = kernels or {}
    kernel_s = {k: sum(e - s for n, s, e in clipped if pat in n) / 1e9
                for k, pat in kernels.items()}
    kernel_n = {k: sum(1 for n, _, _ in clipped if pat in n)
                for k, pat in kernels.items()}
    # idle gaps, each charged to the innermost host span that holds the
    # gap's midpoint (spans are sequential, so the holder is near)
    spans = sorted((s, e, n) for n, s, e in host if n != WINDOW)
    starts = [s for s, _, _ in spans]
    gaps: Dict[str, int] = defaultdict(int)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) // 2
        best, best_len = "host:other", None
        i = bisect.bisect_right(starts, mid)
        for s, e, n in reversed(spans[max(0, i - 64):i]):
            if e >= mid and (best_len is None or e - s < best_len):
                best, best_len = n, e - s
        gaps[best] += g1 - g0
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernel_s": kernel_s,
        "kernel_events": kernel_n,
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": [[n, t / 1e9] for n, t in top_gaps],
    }


def idle_share_percent(summary: Optional[dict]) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, in percent; None without a trace."""
    if not summary or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


class TracedWindow:
    """The profiler over the measured window, when ``on``: ``start`` at
    the window's open, ``stop`` at its close, then ``summary``. The
    benchmark's spans go into the trace while it runs."""

    def __init__(self, on: bool, spans):
        self.on, self.spans = on, spans
        self.dir = self.ann = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python calls would swamp it
        opts.host_tracer_level = 2        # keeps TraceAnnotation spans
        self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.tracing = True
        self.ann = jax.profiler.TraceAnnotation(WINDOW)
        self.ann.__enter__()

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        self.ann.__exit__(None, None, None)
        self.spans.tracing = False
        jax.profiler.stop_trace()

    def summary(self, host_names: Iterable[str],
                kernels: Optional[Dict[str, str]] = None) -> Optional[dict]:
        if not self.on:
            return None
        try:
            dev, host = load_events(self.dir, host_names)
            return summarize(dev, host, kernels)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
