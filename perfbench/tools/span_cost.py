"""What one of the program's spans and counts costs on the host, in each
of ``repro.tracing``'s modes.

    python3 perfbench/tools/span_cost.py [--calls 50000] [--repeats 5]

Prints one JSON line: microseconds per ``with tracing.span(...)`` (an
empty body) and per ``tracing.count(...)``, best of ``--repeats`` timed
loops of ``--calls`` each, off (no recorder, no trace), attached (a
``tracing.Recorder``), profiled (no recorder, while a ``jax.profiler``
trace runs with the benchmark's options, ``perfbench.trace``), and the
benchmark's own ``Spans`` under that trace, for comparison.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from perfbench.spans import Spans  # noqa: E402
from repro import tracing  # noqa: E402


def best_us(fn, calls: int, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(calls)
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / calls


def spans(n: int) -> None:
    for _ in range(n):
        with tracing.span("detector.push"):
            pass


def counts(n: int) -> None:
    for _ in range(n):
        tracing.count("fleet_score.bytes_in", 8)


def bench_spans(rec):
    def loop(n: int) -> None:
        for _ in range(n):
            with rec.span("detector.update"):
                pass
    return loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=50_000)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    k = (args.calls, args.repeats)
    out = {"device": jax.devices()[0].device_kind}
    tracing.detach()
    out["off"] = {"span_us": best_us(spans, *k),
                  "count_us": best_us(counts, *k)}
    tracing.attach(tracing.Recorder())
    out["attached"] = {"span_us": best_us(spans, *k),
                       "count_us": best_us(counts, *k)}
    tracing.detach()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    d = tempfile.mkdtemp(prefix="span_cost_")
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["profiled"] = {"span_us": best_us(spans, *k),
                               "count_us": best_us(counts, *k)}
            rec = Spans()
            rec.tracing = True
            out["benchmark_spans_traced"] = {
                "span_us": best_us(bench_spans(rec), *k)}
        finally:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
