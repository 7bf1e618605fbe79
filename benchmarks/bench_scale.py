"""Fleet-scale benchmark: detector scaling to 131k nodes, pinned in CI.

Four measurements, written to ``BENCH_scale.json``:

  1. ``detector``: microbenchmark of ``StragglerDetector.update`` on
     synthetic full-metric frames at 1k..131k nodes — ms per evaluation
     window (mean/p50/p95), resident buffer bytes, and the number of
     per-node Python objects materialized per window, which must scale
     with the FLAGGED population, not the fleet (the struct-of-arrays
     FleetAssessment contract). Gates: p50 at 16384 nodes under
     ``GATE_16K_MS``; full mode additionally gates the 131072/16384 p50
     ratio under ``SUBLINEAR_RATIO_GATE`` (8x the nodes must cost less
     than 8.2x the window — batched scoring cannot regress to
     superlinear); quick mode gates the 65536-node p50 under
     ``QUICK_65K_GATE_MS`` (the CI scale job's budget).
  2. ``scorer_agreement``: the pallas fleet-score kernel and the NumPy
     reference, each driving a full detector over the same frame
     sequence, must produce bit-identical verdict arrays (flags,
     slowdowns, stall/step-deviant, support masks) at the gated sizes.
  3. ``sim_feed``: ms/window of the 65536-node ``SimCluster`` feed
     (run_window + collect) under background fault churn — the windowed
     (W, N) composition and row-targeted link-state refresh keep this
     free of per-node Python.
  4. ``simulate``: wall-clock of the 2048-node, 72 h ENHANCED
     ``simulate_run`` on the window-granular engine, against the
     pre-refactor step-granular baseline measured interleaved on the
     same config / seed / machine immediately before the refactor
     landed (commit 6c6cb4c): ~8-9x min-to-min on the dev container
     (target 10x; enforced regression gate 6x — see SPEEDUP_GATE).

Run:  PYTHONPATH=src python -m benchmarks.bench_scale [--quick]
          [--nodes N,N,...] [--out PATH] [--budget-s S]

``--quick`` is the CI smoke sizing: detector sizes up to 65536, a
1024-node short engine run under a wall-time budget (exit non-zero if it
blows the budget), with the speedup gate reported but not enforced (CI
machines are not the baseline machine). Full mode adds 131072, the
sublinearity gate and the enforced speedup gate. ``--nodes`` overrides
the detector size list (per-size gates still apply to whichever gated
sizes are present).
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

from repro.core import DetectorConfig, StragglerDetector
from repro.core.telemetry import Frame
from repro.guard import Tier
from repro.simcluster import FaultRates, RunConfig, SimCluster, simulate_run

# Pre-refactor step-granular baseline, measured on the exact BENCH config
# below at the commit preceding this refactor (simulate_run with the
# per-step loop, list-scan FaultInjector, per-node detector objects).
# wall_s is the MIN over 7 interleaved old/new runs on the same machine
# (the least-interference sample; same convention as the new-path
# measurement), so the recorded speedup is conservative. Kept as the
# fixed reference the CI artifact trends against.
PRE_REFACTOR = {
    "commit": "6c6cb4c",
    "wall_s": 32.37,
    "wall_s_samples": [32.37, 34.30, 37.18, 42.69, 32.67, 42.14, 35.13],
    "steps": 7419,
    "config": "2048 nodes, 72 h, ENHANCED, initial_grey_p=0.02, seed 0",
}

# The refactor's target was >=10x; the measured speedup on the dev
# container is ~8-9x min-to-min (recorded in the artifact). The enforced
# gate sits at 6x so CI machine variance cannot flake the job while a
# genuine engine regression still fails loudly.
SPEEDUP_TARGET = 10.0
SPEEDUP_GATE = 6.0

# detector per-window budgets (p50 over warm windows). Dev-container
# measurements sit near 2.4 ms at 16k / 8.6 ms at 65k / 17.6 ms at 131k;
# the gates leave ~2.5x headroom for slower CI machines. The ratio gate
# pins sublinear-or-linear scaling: 8x the nodes in under 8.2x the time.
GATE_16K_MS = 6.6
QUICK_65K_GATE_MS = 26.4           # 4 x the 16k budget for 4 x the nodes
SUBLINEAR_RATIO_GATE = 8.2
# sharded scorer="jax" detector pass at 16k on the forced 8-device CPU
# mesh: ~15 ms p50 on the dev container (device round trips + psum across
# node shards dominate); gate leaves ~2.6x headroom for CI machines
SHARDED_16K_GATE_MS = 40.0

FULL_SIZES = (1024, 4096, 16384, 65536, 131072)
QUICK_SIZES = (1024, 4096, 16384, 65536)
# sizes whose pallas-vs-reference verdict agreement is checked/gated
AGREEMENT_SIZES_QUICK = (16384,)
AGREEMENT_SIZES_FULL = (16384, 131072)

SCALE_CONFIG = dict(tier=Tier.ENHANCED, n_nodes=2048, n_spare=128,
                    duration_h=72.0, initial_grey_p=0.02, seed=0)
QUICK_CONFIG = dict(tier=Tier.ENHANCED, n_nodes=1024, n_spare=64,
                    duration_h=6.0, initial_grey_p=0.05, seed=0)


def synthetic_frame(w: int, n: int, rng, stragglers) -> Frame:
    t = 10.0 * (1.0 + rng.normal(0, 0.004, n))
    for nid, factor in stragglers:
        t[nid] *= factor
    metrics = {
        "step_time": t,
        "gpu_temp": 58.0 + rng.normal(0, 0.8, n),
        "gpu_util": np.clip(rng.normal(0.97, 0.01, n), 0, 1),
        "gpu_freq": np.full(n, 1.93) + rng.normal(0, 0.002, n),
        "gpu_power": 350.0 + rng.normal(0, 3.0, n),
        "nic_errors": np.zeros(n),
        "nic_tx_rate": 50.0 + rng.normal(0, 0.5, n),
        "nic_up": np.ones(n),
    }
    return Frame(t=w * 60.0, step=w * 6,
                 node_ids=np.arange(n, dtype=np.int64),
                 metrics=metrics, valid=np.ones(n, bool))


def _stragglers(n: int, n_stragglers: int):
    return [(i * (n // max(n_stragglers, 1)) + 7, 1.2)
            for i in range(n_stragglers)]


def detector_microbench(n: int, windows: int = 24,
                        n_stragglers: int = 4,
                        scorer: str = "numpy") -> dict:
    """ms/window + materialized-object count for an N-node fleet with a
    handful of genuine stragglers (the realistic steady state)."""
    rng = np.random.RandomState(n)
    stragglers = _stragglers(n, n_stragglers)
    det = StragglerDetector(DetectorConfig(scorer=scorer))
    frames = [synthetic_frame(w, n, rng, stragglers)
              for w in range(windows)]
    per_window_ms = []
    materialized = []
    flagged = []
    for frame in frames:
        t0 = time.perf_counter()
        fa = det.update(frame)
        fa.flagged_assessments()         # what the monitor/policy consume
        per_window_ms.append((time.perf_counter() - t0) * 1e3)
        materialized.append(fa.materialized)
        flagged.append(int(fa.flagged.sum()))
    warm = per_window_ms[2:]             # skip alloc warmup
    return {
        "n_nodes": n,
        "windows": windows,
        "scorer": scorer,
        "ms_per_window_mean": float(np.mean(warm)),
        "ms_per_window_p50": float(np.median(warm)),
        "ms_per_window_p95": float(np.percentile(warm, 95)),
        "memory_bytes": det.memory_nbytes(),
        "flagged_steady": flagged[-1],
        "objects_per_window_max": int(max(materialized)),
        "objects_O_flagged": bool(
            max(materialized) <= max(max(flagged), 1) + n_stragglers),
    }


def scorer_agreement(n: int, windows: int = 6,
                     n_stragglers: int = 4) -> dict:
    """Drive two detectors — NumPy reference scorer vs the pallas
    fleet-score kernel — over the same frames. Every verdict array must
    agree exactly (the kernel's golden contract, checked at fleet scale
    where lane padding and big-N medians actually bite); ``differ`` names
    any that do not. The slowdown magnitude is ``x / median - 1``
    averaged over windows: ``slowdown_ulps`` is its largest difference in
    ulps of that quotient — 0 wherever float32 division rounds as IEEE
    does (the CPU), not necessarily on a TPU. ``bit_identical`` asks for
    both."""
    rng = np.random.RandomState(n + 1)
    stragglers = _stragglers(n, n_stragglers)
    det_ref = StragglerDetector(DetectorConfig(scorer="numpy"))
    det_pl = StragglerDetector(DetectorConfig(scorer="pallas"))
    differ, ulps = set(), 0.0
    for w in range(windows):
        frame = synthetic_frame(w, n, rng, stragglers)
        a = det_ref.update(copy.deepcopy(frame))
        b = det_pl.update(copy.deepcopy(frame))
        pairs = [("flagged", a.flagged, b.flagged),
                 ("stalled", a.stalled, b.stalled),
                 ("step_deviant", a.step_deviant, b.step_deviant)]
        pairs += [(m, a.support_masks[m], b.support_masks.get(m))
                  for m in a.support_masks]
        differ |= {name for name, x, y in pairs
                   if y is None or not np.array_equal(x, y)}
        differ |= set(b.support_masks) - set(a.support_masks)
        quot_ulp = np.float32(2.0 ** -23) * (1.0 + np.abs(a.slowdown))
        ulps = max(ulps, float(np.max(np.abs(a.slowdown - b.slowdown)
                                      / quot_ulp)))
    return {"n_nodes": n, "windows": windows, "differ": sorted(differ),
            "slowdown_ulps": ulps, "flagged": int(a.flagged.sum()),
            "bit_identical": not differ and ulps == 0.0}


def sharded_detection(n: int = 16384, windows: int = 8,
                      n_stragglers: int = 4) -> dict:
    """Full detector pass with ``scorer="jax"`` under an ACTIVE
    multi-device mesh (``make_cpu_mesh`` over however many host devices
    XLA exposes; CI forces 8). The input is constrained over the
    ``fleet_node`` logical axis, so the peer-median rank counts psum
    across node shards — this is the real sharded production path, not
    the single-device jit. Gated on verdict parity with the NumPy
    reference detector over the same frames and on per-window cost."""
    import jax

    from repro import dist
    from repro.launch.mesh import make_cpu_mesh

    rng = np.random.RandomState(n + 2)
    stragglers = _stragglers(n, n_stragglers)
    frames = [synthetic_frame(w, n, rng, stragglers)
              for w in range(windows)]
    det_ref = StragglerDetector(DetectorConfig(scorer="numpy"))
    ref = [det_ref.update(copy.deepcopy(f)) for f in frames]

    det_jax = StragglerDetector(DetectorConfig(scorer="jax"))
    mesh = make_cpu_mesh()
    per_window_ms = []
    agree = True
    with dist.use_mesh(mesh):
        for frame, a in zip(frames, ref):
            t0 = time.perf_counter()
            b = det_jax.update(copy.deepcopy(frame))
            per_window_ms.append((time.perf_counter() - t0) * 1e3)
            agree &= np.array_equal(a.flagged, b.flagged)
            agree &= np.array_equal(a.stalled, b.stalled)
            agree &= np.array_equal(a.step_deviant, b.step_deviant)
    warm = per_window_ms[2:]             # skip trace/compile warmup
    return {
        "n_nodes": n,
        "windows": windows,
        "n_devices": len(jax.devices()),
        "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
        "ms_per_window_p50": float(np.median(warm)),
        "ms_per_window_p95": float(np.percentile(warm, 95)),
        "verdict_parity": bool(agree),
    }


def sim_feed_bench(n: int = 65536, windows: int = 10,
                   warmup: int = 2) -> dict:
    """ms/window of the simulated fleet feed (run_window + collect) at
    scale, under background grey-fault churn (no fail-stops: a crashed
    fleet stops stepping and would measure nothing)."""
    rates = FaultRates(fail_stop=0, admission_grey_p=0)
    c = SimCluster(n, 16, reserve=32, rates=rates, seed=9)
    for _ in range(warmup):
        c.run_window(6)
        c.collect()
    ms = []
    steps = 0
    for _ in range(windows):
        t0 = time.perf_counter()
        rec = c.run_window(6)
        c.collect()
        ms.append((time.perf_counter() - t0) * 1e3)
        steps += rec["steps_run"]
    return {
        "n_nodes": n,
        "windows": windows,
        "steps": steps,
        "ms_per_window_mean": float(np.mean(ms)),
        "ms_per_window_p50": float(np.median(ms)),
        "ms_per_window_p95": float(np.percentile(ms, 95)),
        "fleet_memory_bytes": c.fleet.memory_nbytes(),
    }


def sim_scale_bench(quick: bool, repeats: int = 1) -> dict:
    cfg = QUICK_CONFIG if quick else SCALE_CONFIG
    walls = []
    r = None
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        r = simulate_run(RunConfig(**cfg))
        walls.append(time.perf_counter() - t0)
    # min over repeats: wall-clock gates need the least-interference
    # sample on shared machines (same convention as the baseline)
    wall = min(walls)
    out = {
        "config": {k: (int(v) if k == "tier" else v)
                   for k, v in cfg.items()},
        "wall_s": wall,
        "wall_s_all": walls,
        "steps": r.steps,
        "crashes": r.crashes,
        "mfu": r.mfu,
        "mttf_h": r.mttf_h,
        "events": len(r.events),
    }
    if not quick:
        out["baseline"] = PRE_REFACTOR
        out["speedup_vs_prerefactor"] = PRE_REFACTOR["wall_s"] / wall
        out["speedup_target"] = SPEEDUP_TARGET
        out["speedup_gate"] = SPEEDUP_GATE
    return out


def scale_summary(quick: bool = True) -> dict:
    """Compact detector-scaling summary for embedding in other
    benchmark artifacts (benchmarks.run_all). Engine wall-clock numbers
    live in BENCH_scale.json only."""
    sizes = (1024, 4096) if quick else (1024, 4096, 16384)
    return {
        "detector": [detector_microbench(n, windows=12) for n in sizes],
    }


def _fmt_bytes(b: int) -> str:
    return f"{b / 2**20:.1f} MiB"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizing (<=65536 nodes, short run)")
    ap.add_argument("--nodes", default=None,
                    help="comma-separated detector size override, e.g. "
                         "1024,16384,65536")
    ap.add_argument("--budget-s", type=float, default=300.0,
                    help="wall-time budget for the quick run (CI gate)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_scale.json"))
    args = ap.parse_args(argv)

    if args.nodes:
        sizes = tuple(int(s) for s in args.nodes.split(",") if s.strip())
    else:
        sizes = QUICK_SIZES if args.quick else FULL_SIZES
    agree_sizes = [n for n in (AGREEMENT_SIZES_QUICK if args.quick
                               else AGREEMENT_SIZES_FULL) if n in sizes]

    t0 = time.perf_counter()
    detector = [detector_microbench(n) for n in sizes]
    by_n = {d["n_nodes"]: d for d in detector}
    agreement = [scorer_agreement(n) for n in agree_sizes]
    sharded = sharded_detection() if 16384 in sizes else None
    sim_feed = sim_feed_bench() if 65536 in sizes else None
    sim = sim_scale_bench(quick=args.quick, repeats=1 if args.quick else 3)
    out = {
        "benchmark": "guard_scale",
        "mode": "quick" if args.quick else "full",
        "sizes": list(sizes),
        "detector": detector,
        "scorer_agreement": agreement,
        "sharded_detection": sharded,
        "sim_feed": sim_feed,
        "simulate": sim,
        "gates": {
            "detector_16k_p50_ms_max": GATE_16K_MS,
            "detector_65k_p50_ms_max_quick": QUICK_65K_GATE_MS,
            "detector_131k_over_16k_ratio_max": SUBLINEAR_RATIO_GATE,
            "sharded_jax_16k_p50_ms_max": SHARDED_16K_GATE_MS,
        },
        "total_wall_s": time.perf_counter() - t0,
    }
    if 16384 in by_n and 131072 in by_n:
        out["ratio_131k_over_16k"] = (
            by_n[131072]["ms_per_window_p50"] /
            max(by_n[16384]["ms_per_window_p50"], 1e-9))

    print(f"{'n_nodes':>8s}{'ms p50':>9s}{'ms p95':>9s}{'memory':>11s}"
          f"{'objects/win':>13s}{'flagged':>9s}")
    for d in detector:
        print(f"{d['n_nodes']:8d}{d['ms_per_window_p50']:9.2f}"
              f"{d['ms_per_window_p95']:9.2f}"
              f"{_fmt_bytes(d['memory_bytes']):>11s}"
              f"{d['objects_per_window_max']:13d}{d['flagged_steady']:9d}")
    for a in agreement:
        print(f"pallas-vs-ref verdicts @{a['n_nodes']}: "
              f"{'bit-identical' if a['bit_identical'] else 'DISAGREE'}")
    if sharded:
        print(f"sharded jax @{sharded['n_nodes']} on "
              f"{sharded['n_devices']}-device mesh "
              f"{sharded['mesh_shape']}: "
              f"p50 {sharded['ms_per_window_p50']:.1f} ms/window, "
              f"verdicts {'match numpy' if sharded['verdict_parity'] else 'DISAGREE'}")
    if sim_feed:
        print(f"sim feed @{sim_feed['n_nodes']}: "
              f"p50 {sim_feed['ms_per_window_p50']:.0f} ms/window "
              f"(fleet {_fmt_bytes(sim_feed['fleet_memory_bytes'])})")
    print(f"simulate: {sim['config']['n_nodes']} nodes, "
          f"{sim['config']['duration_h']:.0f}h -> {sim['wall_s']:.1f}s "
          f"({sim['steps']} steps, {sim['crashes']} crashes)")

    ok = True
    if not all(d["objects_O_flagged"] for d in detector):
        print("FAIL: detector materialized O(N) objects per window",
              file=sys.stderr)
        ok = False
    if not all(a["bit_identical"] for a in agreement):
        print("FAIL: pallas scorer disagrees with the reference",
              file=sys.stderr)
        ok = False
    if sharded is not None:
        if not sharded["verdict_parity"]:
            print("FAIL: sharded jax scorer verdicts disagree with numpy",
                  file=sys.stderr)
            ok = False
        if sharded["ms_per_window_p50"] > SHARDED_16K_GATE_MS:
            print(f"FAIL: sharded jax 16k detector p50 "
                  f"{sharded['ms_per_window_p50']:.1f} ms > "
                  f"{SHARDED_16K_GATE_MS}", file=sys.stderr)
            ok = False
    if 16384 in by_n and \
            by_n[16384]["ms_per_window_p50"] > GATE_16K_MS:
        print(f"FAIL: 16k detector p50 "
              f"{by_n[16384]['ms_per_window_p50']:.2f} ms > {GATE_16K_MS}",
              file=sys.stderr)
        ok = False
    if "ratio_131k_over_16k" in out and \
            out["ratio_131k_over_16k"] >= SUBLINEAR_RATIO_GATE:
        print(f"FAIL: 131k/16k per-window ratio "
              f"{out['ratio_131k_over_16k']:.2f} >= "
              f"{SUBLINEAR_RATIO_GATE} (superlinear scaling)",
              file=sys.stderr)
        ok = False
    if args.quick:
        if 65536 in by_n and \
                by_n[65536]["ms_per_window_p50"] > QUICK_65K_GATE_MS:
            print(f"FAIL: 65k detector p50 "
                  f"{by_n[65536]['ms_per_window_p50']:.2f} ms > "
                  f"{QUICK_65K_GATE_MS}", file=sys.stderr)
            ok = False
        if sim["wall_s"] > args.budget_s:
            print(f"FAIL: quick scale run {sim['wall_s']:.1f}s over the "
                  f"{args.budget_s:.0f}s budget", file=sys.stderr)
            ok = False
    else:
        speedup = sim["speedup_vs_prerefactor"]
        print(f"speedup vs pre-refactor step-granular path: {speedup:.1f}x "
              f"(baseline {PRE_REFACTOR['wall_s']:.1f}s @ "
              f"{PRE_REFACTOR['commit']}; target {SPEEDUP_TARGET:.0f}x, "
              f"gate {SPEEDUP_GATE:.0f}x)")
        if speedup < SPEEDUP_GATE:
            print(f"FAIL: speedup below the {SPEEDUP_GATE:.0f}x gate",
                  file=sys.stderr)
            ok = False
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}  ({out['total_wall_s']:.0f}s)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
