"""A cell's run with the program's own spans in view.

    python3 perfbench/tools/program_split.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--attach]

Runs the cell as ``perfbench/run.py`` does, which prints its result line,
then prints one line ``program: {...}``:

* ``setup``: Guard's span totals (s), calls and longest calls (ms) and
  its counters (``repro.tracing``) over set-up, from the driver's traced
  window object on: in the guarded cells, the fast snapshot at step 1
  split into ``ckpt.snapshot.copy`` and ``ckpt.snapshot.write``;
* ``outside``: the benchmark's own spans over the window, alike;
* ``window``: the program's over the window. In a traced run they come
  from the record the program keeps over a trace, and the driver's idle
  gaps are also charged to the program's spans, besides the benchmark's
  own (the driver's ``HOST_SPANS`` extended by
  ``repro.tracing.SPAN_NAMES``), in the result line's ``breakdown``.
  With ``--attach`` (untraced runs only) they come from a recorder
  attached over the whole window.

A run with ``--attach`` and one without, on the same seed, differ only
in that recorder: their result lines give the cost of recording in the
cell's end-to-end metrics.
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import run, trace  # noqa: E402
from repro import tracing  # noqa: E402


def digest(stats, counters) -> dict:
    """``stats``: name -> (seconds, calls, longest seconds)."""
    return {"total_s": {k: v[0] for k, v in stats.items()},
            "count": {k: int(v[1]) for k, v in stats.items()},
            "max_ms": {k: 1e3 * v[2] for k, v in stats.items()},
            "counters": dict(counters)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--attach", action="store_true")
    args = ap.parse_args(argv)
    if args.attach and args.trace:
        ap.error("--attach measures untraced runs")
    bench = run.load_json("BENCHMARK.json")
    _, cfg, _ = run.cell_spec(bench, args.workload)
    drv = importlib.import_module("perfbench.drivers." + cfg["driver"])
    drv.HOST_SPANS = tuple(drv.HOST_SPANS) + tracing.SPAN_NAMES
    setup, window = tracing.Recorder(), tracing.Recorder()
    kept = {}

    class SplitWindow(trace.TracedWindow):
        def __init__(self, on, spans):
            super().__init__(on, spans)
            tracing.attach(setup)

        def start(self):
            kept["setup_counters"] = dict(tracing.counters)
            tracing.detach()
            if args.attach:
                tracing.attach(window)
            super().start()

        def stop(self):
            if args.attach:
                kept["window_counters"] = dict(tracing.counters)
            tracing.detach()
            kept["outside"] = {k: (sum(v), len(v), max(v))
                               for k, v in self.spans.durations.items() if v}
            super().stop()

    drv.TracedWindow = SplitWindow
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds),
                   "--trace", str(args.trace)])
    out = {"setup": digest(setup.stats, kept.get("setup_counters", {}))}
    if "outside" in kept:
        out["outside"] = digest(kept["outside"], {})
    if args.attach:
        out["window"] = digest(window.stats, kept.get("window_counters", {}))
    elif args.trace and tracing.profiled is not None:
        out["window"] = digest(tracing.profiled.stats, tracing.counters)
    print("program: " + json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
