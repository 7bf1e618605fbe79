"""The general traffic generator: every mix under ``perfbench/mixes`` is a
file of parameters that one of these functions reads.

Training traffic is the token batches of the guarded job. Fleet traffic
is telemetry frames: one row per accelerator, eight metrics, drawn as
``benchmarks/bench_scale.py``'s ``synthetic_frame`` draws them (copied
here so that the yardstick cannot move with the program), with the
mix's stragglers or correlated incident placed from the seed.
Everything is a pure function of the seed, which may be any whole
number.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# metric columns of a fleet frame, in the detector's vocabulary
FLEET_METRICS = ("step_time", "gpu_temp", "gpu_util", "gpu_freq",
                 "gpu_power", "nic_errors", "nic_tx_rate", "nic_up")


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...); any size of seed."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def token_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> Dict[str, np.ndarray]:
    """The batch of training step ``step`` (1-based): uniform token ids,
    labels shifted by one. Every step's rows differ."""
    toks = rng_for(seed, 1, step).integers(0, vocab, (batch, seq_len + 1),
                                           dtype=np.int64)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


def fleet_faults(seed: int, rows: int, mix: dict) -> Tuple[np.ndarray, ...]:
    """(slow rows, their step-time factors, incident rows) for a mix.

    ``stragglers``: ``count`` rows placed uniformly from the seed, each
    with a factor drawn in ``factor_range``. ``incident``: one contiguous
    block of ``rows_share`` of the rows at ``factor``, whose temperature
    and clock deviate with it.
    """
    rng = rng_for(seed, 2)
    slow = np.zeros(0, np.int64)
    factors = np.zeros(0)
    inc = np.zeros(0, np.int64)
    st = mix.get("stragglers")
    if st:
        slow = np.sort(rng.choice(rows, st["count"], replace=False))
        lo, hi = st["factor_range"]
        factors = rng.uniform(lo, hi, st["count"])
    ic = mix.get("incident")
    if ic:
        n = int(round(ic["rows_share"] * rows))
        start = int(rng.integers(0, rows - n + 1))
        inc = np.arange(start, start + n)
        slow = np.concatenate([slow, inc])
        factors = np.concatenate([factors, np.full(n, ic["factor"])])
    return slow, factors, inc


def fleet_frame(seed: int, rows: int, mix: dict, f: int,
                faults=None) -> Dict[str, np.ndarray]:
    """Frame ``f`` of metric columns (float64, as telemetry comes).

    Noise is drawn anew for each frame; the faults of the mix stay on
    the same rows in every frame, as a sustained fault does.
    """
    slow, factors, inc = faults or fleet_faults(seed, rows, mix)
    z = rng_for(seed, 3, f).standard_normal((6, rows))
    t = 10.0 * (1.0 + 0.004 * z[0])
    t[slow] *= factors
    m = {
        "step_time": t,
        "gpu_temp": 58.0 + 0.8 * z[1],
        "gpu_util": np.clip(0.97 + 0.01 * z[2], 0, 1),
        "gpu_freq": 1.93 + 0.002 * z[3],
        "gpu_power": 350.0 + 3.0 * z[4],
        "nic_errors": np.zeros(rows),
        "nic_tx_rate": 50.0 + 0.5 * z[5],
        "nic_up": np.ones(rows),
    }
    if inc.size:
        m["gpu_temp"][inc] += mix["incident"]["temp_rise"]
        m["gpu_freq"][inc] *= mix["incident"]["freq_factor"]
    return m


def fleet_frames(seed: int, rows: int, mix: dict, count: int
                 ) -> List[Dict[str, np.ndarray]]:
    faults = fleet_faults(seed, rows, mix)
    return [fleet_frame(seed, rows, mix, f, faults) for f in range(count)]
