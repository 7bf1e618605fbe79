"""Plain NumPy reference of fleet detection and the tiered policy
(arXiv:2605.17879 §4.2), on the frames the benchmark fed the program.

For a frame f it needs the frames f - (N - 1) - (C - 1) .. f only: the
verdicts of a frame depend on the last N frames (K of N persistence),
and the latch of a node is set exactly when its raw verdict was set in
one of the last C frames (it releases after C clean frames). It imports
nothing of the program; arithmetic on the history is float32, as the
configuration states, or bfloat16 for the lower-precision control.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

F32 = np.float32
IMMEDIATE, DEFER, PENDING = "immediate_restart", "defer_to_checkpoint", \
    "pending_verification"
# +1: higher is unhealthy; -1: lower is unhealthy
DIRECTION = {"step_time": 1, "gpu_temp": 1, "gpu_util": -1, "gpu_freq": -1,
             "gpu_power": -1, "nic_errors": 1, "nic_tx_rate": -1,
             "nic_up": -1}


def _median(x: np.ndarray) -> np.ndarray:
    n = x.shape[-1]
    h = n // 2
    if n % 2:
        return np.partition(x, h, axis=-1)[..., h:h + 1]
    p = np.partition(x, (h - 1, h), axis=-1)
    return (p[..., h - 1:h] + p[..., h:h + 1]) / F32(2.0)


def _as_precision(x: np.ndarray, precision: str) -> np.ndarray:
    x = np.asarray(x, F32)
    if precision == "bfloat16":
        # round to nearest even in the top 16 bits
        b = x.view(np.uint32).astype(np.uint64)
        b = (b + 0x7FFF + ((b >> 16) & 1)) >> 16 << 16
        x = b.astype(np.uint32).view(F32)
    return x


class FleetReference:
    def __init__(self, frame: Callable[[int], Dict[str, np.ndarray]],
                 metrics: Sequence[str], det: dict, pol: dict,
                 precision: str = "float32"):
        self.frame, self.metrics = frame, list(metrics)
        self.det, self.pol, self.precision = det, pol, precision
        self._scored: Dict[int, tuple] = {}

    def _score(self, g: int) -> tuple:
        """(deviant (M, N) bool, masked relative step-time excess (N,))
        of frame g's row."""
        if g not in self._scored:
            det = self.det
            x = np.stack([_as_precision(self.frame(g)[m], self.precision)
                          for m in self.metrics])
            d = np.asarray([DIRECTION[m] for m in self.metrics],
                           F32)[:, None]
            med = _median(x)
            diff = x - med
            mad = _median(np.abs(diff))
            floor = np.maximum(np.abs(med) * F32(det["mad_floor_frac"]),
                               F32(1e-9))
            scale = np.maximum(mad / F32(0.6745), floor)
            dev = (diff / scale) * d > F32(det["z_threshold"])
            j = self.metrics.index("step_time")
            rel = x[j] / np.maximum(med[j], F32(1e-9)) - F32(1.0)
            dev[j] &= rel > F32(det["slowdown_floor"])
            self._scored[g] = (dev, np.where(dev[j], rel, F32(0.0)))
        return self._scored[g]

    def _raw(self, g: int) -> dict:
        det = self.det
        rows = list(range(max(0, g - det["window"] + 1), g + 1))
        need = det["persistence"] if len(rows) >= det["persistence"] \
            else len(rows) + 1
        devs = [self._score(r)[0] for r in rows]
        counts = np.sum(devs, axis=0)                     # (M, N)
        j = self.metrics.index("step_time")
        deviant = counts[j] >= need
        slow_sum = np.stack([self._score(r)[1] for r in rows]).sum(0)
        slowdown = np.where(deviant, slow_sum / np.maximum(
            counts[j], 1).astype(F32), F32(0.0))
        st = np.asarray(self.frame(g)["step_time"], np.float64)
        stalled = st > det["stall_factor"] * np.median(st)
        support = {m: counts[i] >= need for i, m in enumerate(self.metrics)
                   if m != "step_time"}
        n_support = np.sum(list(support.values()), axis=0)
        raw = stalled | deviant | (n_support >= det["min_support"])
        return {"raw": raw, "step_deviant": deviant, "stalled": stalled,
                "slowdown": slowdown, "support": support}

    def at(self, f: int) -> dict:
        """Verdicts and decisions of frame f."""
        out = self._raw(f)
        flagged = out["raw"].copy()
        for g in range(max(0, f - self.det["clear_windows"] + 1), f):
            flagged |= self._raw(g)["raw"]
        out["flagged"] = flagged
        sev, mod = self.pol["severe_slowdown"], self.pol["moderate_slowdown"]
        dec: List[tuple] = []
        for i in np.flatnonzero(flagged):
            s = out["slowdown"][i]
            act = IMMEDIATE if out["stalled"][i] or s >= sev else \
                DEFER if s >= mod else PENDING
            dec.append((int(i), act))
        out["decisions"] = dec
        return out


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """Mismatches of one frame: verdict arrays (exact), decisions
    (exact), and the widest gap of ``slowdown`` in float32 ulps of the
    quotient it comes from, x / median = 1 + slowdown (TPU division need
    not round as IEEE does)."""
    verdict = 0
    for key in ("flagged", "step_deviant", "stalled"):
        verdict += int(np.sum(np.asarray(prog[key]) != ref[key]))
    for m, mask in ref["support"].items():
        verdict += int(np.sum(np.asarray(prog["support"][m]) != mask))
    ps = np.asarray(prog["slowdown"], F32)
    rs = ref["slowdown"].astype(F32)
    ulps = np.abs(ps.astype(np.float64) - rs) / np.spacing(F32(1.0) + rs)
    return {"verdict_mismatch": verdict,
            "decision_mismatch": int(list(prog["decisions"]) !=
                                     ref["decisions"]),
            "slowdown_ulps": float(ulps.max(initial=0.0))}
