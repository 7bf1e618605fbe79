"""Record a small trace on the chip for ``tests/test_trace.py``: a few
frames of fleet detection at 131,072 rows with the profiler on, saved
as the (device ops, host spans) events that ``trace.load_events``
returns.

    python3 perfbench/tools/record_trace.py <out.json>
"""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out: str, frames: int = 4) -> None:
    import jax
    import numpy as np

    from perfbench import trace, traffic
    from perfbench.spans import Spans
    from repro.core.detector import DetectorConfig, StragglerDetector
    from repro.core.telemetry import Frame

    assert jax.devices()[0].platform == "tpu"
    rows = 131072
    pool = traffic.fleet_frames(1, rows, {"stragglers": {
        "count": 13, "factor_range": [1.05, 1.5]}}, frames + 2)
    det = StragglerDetector(DetectorConfig(scorer="pallas"))
    ids = np.arange(rows)
    ok = np.ones(rows, bool)
    for f in range(2):
        det.update(Frame(t=f, step=f, node_ids=ids, metrics=pool[f],
                         valid=ok))
    spans = Spans()
    win = trace.TracedWindow(True, spans)
    win.start()
    for f in range(2, frames + 2):
        with spans.span("detector.update"):
            det.update(Frame(t=f, step=f, node_ids=ids, metrics=pool[f],
                             valid=ok))
    win.stop()
    dev, host = trace.load_events(win.dir, ["detector.update"],
                                  raw_names=True)
    shutil.rmtree(win.dir, ignore_errors=True)
    w = [h for h in host if h[0] == trace.WINDOW][0]
    dev = [e for e in dev if e[2] > w[1] and e[1] < w[2]]
    with open(out, "w") as fh:
        json.dump({"frames": frames, "device": dev, "host": host}, fh)
    print(json.dumps(trace.summarize(
        [(trace.op_name(n), a, b) for n, a, b in dev], host,
        {"fleet_score": "[tpu_custom_call]"})))


if __name__ == "__main__":
    main(sys.argv[1])
