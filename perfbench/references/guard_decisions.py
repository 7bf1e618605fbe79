"""Plain reference of Guard's decisions on one guarded job, fed the step
times that the run itself observed.

Guard decides from wall-clock step times, which no one can fix in
advance on a chip; given the same observations its decisions are
determined. So this reference replays the run's own record: every step
time the step hook was told, in order, every checkpoint boundary, and
the synthetic peer rows of every frame the hook built. From these alone
it works out, as the paper (§4.2) and the configuration state them:

- the windowing: the mean of ``window_steps`` step times per frame, the
  first ``warmup_windows`` windows after a start or a restart dropped;
- the detector: per frame, robust z of each node against the median and
  MAD of its peers (MAD floored at a share of the median), a relative
  excess over the median above a floor, held for K of the last N frames;
  a stall at ``stall_factor`` x the median; a latch that releases after
  ``clear_windows`` clean frames; a replaced node's history column
  refilled with its first reading;
- the policy: for each latched node, IMMEDIATE at a stall or a sustained
  slowdown >= ``severe``, DEFER >= ``moderate``, PENDING otherwise;
- the manager: IMMEDIATE swaps the node out and restarts at once; DEFER
  swaps at the next checkpoint boundary if still latched, and the job
  restarts at its next step.

It imports nothing of the program. Arithmetic on the history is float32,
as the configuration states.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

F32 = np.float32
IMMEDIATE, DEFER, PENDING = "immediate_restart", "defer_to_checkpoint", \
    "pending_verification"


def _median(x: np.ndarray) -> np.ndarray:
    """Median of each row (last axis), float32: mean of the two middle
    order statistics for an even count."""
    s = np.sort(x, axis=-1)
    n = x.shape[-1]
    h = n // 2
    if n % 2:
        return s[..., h:h + 1]
    return (s[..., h - 1:h] + s[..., h:h + 1]) / F32(2.0)


def score(rows: np.ndarray, det: dict) -> tuple:
    """(deviant, relative excess) of each node in each history row."""
    med = _median(rows)
    diff = rows - med
    mad = _median(np.abs(diff))
    floor = np.maximum(np.abs(med) * F32(det["mad_floor_frac"]), F32(1e-9))
    scale = np.maximum(mad / F32(0.6745), floor)
    dev = (diff / scale) * F32(1.0) > F32(det["z_threshold"])
    rel = rows / np.maximum(med, F32(1e-9)) - F32(1.0)
    return dev & (rel > F32(det["slowdown_floor"])), rel


def replay(log: Sequence[tuple], peer_rows: Sequence[np.ndarray],
           guard: dict) -> Dict[str, list]:
    """Decisions of Guard on a recorded run.

    ``log``: ("call", step, told_seconds) for each step the hook saw and
    ("ckpt", step) for each checkpoint boundary, in order. ``peer_rows``:
    the peer step times of each frame, in order.
    """
    det, pol = guard["detector"], guard["policy"]
    w_steps, warmup = guard["window_steps"], guard["warmup_windows"]
    depth, k_of_n = det["window"], det["persistence"]
    n = 1 + guard["n_peers"]
    hist: List[np.ndarray] = []
    latched = np.zeros(n, bool)
    clean = np.zeros(n, np.int64)
    in_job = np.ones(n, bool)
    deferred: List[int] = []
    refill_own = pending_restart = False
    window: List[float] = []
    seen = 0
    control_t = 0.0
    frames, restarts = [], []

    def swap(col: int) -> None:
        nonlocal refill_own
        latched[col], clean[col] = False, 0
        if col == 0:
            refill_own = True          # a fresh node reports in column 0
        else:
            in_job[col] = False        # the peer's column keeps its id

    for ev in log:
        if ev[0] == "ckpt":
            due = [c for c in dict.fromkeys(deferred)
                   if in_job[c] and latched[c]]
            deferred.clear()
            for c in due:
                swap(c)
            pending_restart = pending_restart or bool(due)
            continue
        _, step, told = ev
        if pending_restart:
            pending_restart, window, seen = False, [], 0
            restarts.append(step)
            continue
        control_t += told
        if control_t >= guard["pending_patience_s"]:
            raise ValueError("run longer than the pending patience: the "
                             "reference does not cover that path")
        window.append(told)
        if len(window) < w_steps:
            continue
        seen += 1
        if seen <= warmup:
            window = []
            continue
        own = float(np.asarray(window, np.float64).mean())
        window = []
        if len(frames) >= len(peer_rows):
            break                      # the run built fewer frames
        row = np.concatenate([[own], peer_rows[len(frames)]])
        if refill_own:
            for h in hist:
                h[0] = F32(own)
            refill_own = False
        hist.append(row.astype(F32))
        hist = hist[-depth:]
        rows = np.stack(hist)
        sdev, rel = score(rows, det)
        need = k_of_n if len(hist) >= k_of_n else len(hist) + 1
        count = sdev.sum(0)
        deviant = count >= need
        slow_sum = np.where(sdev, rel, F32(0.0)).sum(0)
        slowdown = np.where(deviant,
                            slow_sum / np.maximum(count, 1).astype(F32),
                            F32(0.0))
        stalled = row > det["stall_factor"] * np.median(row)
        raw = stalled | deviant
        clean[:] = np.where(raw, 0, np.where(latched, clean + 1, clean))
        latched[:] = raw | (latched & (clean < det["clear_windows"]))
        decisions = []
        for c in np.flatnonzero(latched):
            if stalled[c] or slowdown[c] >= pol["severe_slowdown"]:
                decisions.append((int(c), IMMEDIATE))
            elif slowdown[c] >= pol["moderate_slowdown"]:
                decisions.append((int(c), DEFER))
            else:
                decisions.append((int(c), PENDING))
        frames.append({"step": step, "own": own, "flagged": latched.copy(),
                       "step_deviant": deviant, "stalled": stalled,
                       "slowdown": slowdown, "decisions": decisions})
        restart = False
        for c, act in decisions:
            if not in_job[c]:
                continue
            if act == DEFER and c not in deferred:
                deferred.append(c)
            elif act == IMMEDIATE:
                deferred = [d for d in deferred if d != c]
                swap(c)
                restart = True
        if restart:
            restarts.append(step)
            window, seen = [], 0
    return {"frames": frames, "restarts": restarts}


def compare(program: Dict[str, list], ref: Dict[str, list]) -> Dict[str, int]:
    """Mismatch counts between the program's record and the reference:
    frames whose step or windowed own time differ, verdicts (flag, step
    deviance, stall, slowdown per node) that differ, frames whose
    decisions differ, and restarts at other steps."""
    pf, rf = program["frames"], ref["frames"]
    window = abs(len(pf) - len(rf))
    verdict = decision = 0
    for p, r in zip(pf, rf):
        window += int(p["step"] != r["step"] or p["own"] != r["own"])
        for key in ("flagged", "step_deviant", "stalled", "slowdown"):
            verdict += int(np.sum(np.asarray(p[key]) != r[key]))
        decision += int(list(p["decisions"]) != r["decisions"])
    pr, rr = program["restarts"], ref["restarts"]
    restart = abs(len(pr) - len(rr)) + sum(a != b for a, b in zip(pr, rr))
    return {"window_mismatch": window, "verdict_mismatch": verdict,
            "decision_mismatch": decision, "restart_mismatch": restart}
