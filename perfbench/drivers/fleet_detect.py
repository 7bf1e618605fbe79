"""Driver of the fleet-detection cells: one deployment's detector,
``StragglerDetector`` with the configuration's scorer, then
``TieredPolicy``, fed telemetry frames in a closed loop: the next frame
goes in when the policy's decisions for the last one are out.

Set-up makes a pool of frames from the seed, compiles the scorer for
the one shape it uses (one new ring row a frame), and feeds
``setup_frames`` frames so that the ring is full and the latches are
in their steady state. The window then feeds frame after frame, each a
new ``Frame`` over a pool entry, for ``--seconds``.

After the window: a sample of the frames, drawn from the seed, is
recomputed by ``references/fleet_verdicts.py`` and compared.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from perfbench import traffic
from perfbench.references import fleet_verdicts
from perfbench.trace import TracedWindow

HOST_SPANS = ("detector.update", "policy.decide")


def _record(fa, decisions) -> dict:
    return {"flagged": fa.flagged, "step_deviant": fa.step_deviant,
            "stalled": fa.stalled, "slowdown": fa.slowdown,
            "support": dict(fa.support_masks),
            "decisions": [(int(d.node_id), d.action.value)
                          for d in decisions]}


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        trace: bool, t0: float, spans) -> dict:
    import jax

    from repro.core.detector import DetectorConfig, StragglerDetector
    from repro.core.policy import PolicyConfig, TieredPolicy
    from repro.core.telemetry import Frame

    rows = cfg["hosts"] * cfg["accelerators_per_host"]
    metrics = list(cfg["metrics"])
    pool = traffic.fleet_frames(seed, rows, mix, cfg["frame_pool"])
    det = StragglerDetector(DetectorConfig(**cfg["detector"]))
    pol = TieredPolicy(PolicyConfig(**cfg["policy"]))
    ids = np.arange(rows, dtype=np.int64)
    valid = np.ones(rows, bool)

    def metrics_of(f: int) -> Dict[str, np.ndarray]:
        return pool[f % len(pool)]

    def frame(f: int):
        return Frame(t=60.0 * f, step=6 * f, node_ids=ids,
                     metrics=metrics_of(f), valid=valid)

    for f in range(cfg["setup_frames"]):
        pol.decide(det.update(frame(f)))
    first = cfg["setup_frames"]
    rng = traffic.rng_for(seed, 4)
    sample = set((first + rng.choice(cfg["sample_span"],
                                     cfg["sample_frames"],
                                     replace=False)).tolist())
    kept = {}
    lat = []
    spans.durations.clear()
    traced = TracedWindow(trace, spans)
    traced.start()
    w0 = time.perf_counter()
    f = first
    while True:
        fr = frame(f)
        t_in = time.perf_counter()
        with spans.span("detector.update"):
            fa = det.update(fr)
        with spans.span("policy.decide"):
            decisions = pol.decide(fa)
        t_out = time.perf_counter()
        lat.append(t_out - t_in)
        if f in sample:
            kept[f] = _record(fa, decisions)
        f += 1
        if t_out - w0 >= seconds:
            break
    w1 = time.perf_counter()
    kept[f - 1] = _record(fa, decisions)
    traced.stop()
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    summary = traced.summary(HOST_SPANS, cfg["kernels"])
    frames = f - first
    e2e = {"detect_ms_p99": float(np.percentile(lat, 99)) * 1e3,
           "setup_s": w0 - t0}
    layer = {"frames": frames,
             "policy_ms_per_frame":
             1e3 * spans.total("policy.decide") / frames,
             "rows": rows, "metrics": len(metrics), "window_s": w1 - w0}
    del det, pool

    # ---- correctness on the sampled frames
    regen = {}

    def ref_metrics(g: int):
        k = g % cfg["frame_pool"]
        if k not in regen:
            regen[k] = traffic.fleet_frame(seed, rows, mix, k)
        return regen[k]

    ref = fleet_verdicts.FleetReference(ref_metrics, metrics,
                                        cfg["detector"], cfg["policy"])
    checks = {"verdict_mismatch": 0, "decision_mismatch": 0,
              "slowdown_ulps": 0.0}
    for g in sorted(kept):
        c = fleet_verdicts.compare(kept[g], ref.at(g))
        checks["verdict_mismatch"] += c["verdict_mismatch"]
        checks["decision_mismatch"] += c["decision_mismatch"]
        checks["slowdown_ulps"] = max(checks["slowdown_ulps"],
                                      c["slowdown_ulps"])
    flagged = int(np.sum(kept[f - 1]["flagged"]))
    counters = {"frames": frames, "frames_checked": len(kept),
                "flagged_last_frame": flagged}
    return {"e2e": e2e, "layer": layer, "checks": checks,
            "counters": counters, "trace": summary, "attempted": frames,
            "failed": 0, "memory_peak_bytes": peak,
            "window": (w0, w1), "accuracy": None}
