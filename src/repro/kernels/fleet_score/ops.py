# guardlint: hot  (fleet-sized arrays live here: float32, no per-node loops)
"""Public fleet-score entry point: one call scores R ring-buffer rows.

``score_rows`` dispatches between three interchangeable backends:

  numpy    vectorized partition-based reference (``ref.py``) — the
           single-host production path (no device round trip).
  jax      ``score_rows_jnp`` under ``jax.jit`` — the shardable path.
           When a ``repro.dist`` mesh context is active the input is
           constrained over the ``fleet_node`` logical axis, so the
           peer-median rank counts psum across node shards and the
           elementwise verdicts stay fully partitioned.
  pallas   the fused Pallas kernel, compiled on an accelerator and
           interpreted on the CPU backend; lane dim NaN-padded to the
           128 tile.

All three agree bit-for-bit on the verdict masks. On the CPU they also
agree, for non-degenerate inputs, on the continuous outputs (same
correctly-rounded float32 ops in the same order) — the golden sweep in
``tests/test_detector_golden.py`` pins that contract. TPU float32
division need not round as IEEE does, so there the relative excess
``x / median - 1`` may differ from NumPy's in its last bits;
``chip_smoke.py`` bounds it.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import numpy as np

from repro import tracing
from repro.kernels.fleet_score.ref import score_rows_ref

BACKENDS = ("numpy", "jax", "pallas")
_LANE = 128          # f32 TPU lane tile; pallas inputs pad to multiples


@functools.lru_cache(maxsize=64)
def _compiled(backend: str, shape: Tuple[int, int, int],
              dirs: Tuple[float, ...], st_j: Optional[int],
              z_threshold: float, slowdown_floor: float,
              mad_floor_frac: float, n_valid: Optional[int],
              ctx) -> object:
    """Jitted scorer for one (backend, shape, config, mesh) signature.

    ``ctx`` is the active DistContext (or None) — part of the cache key
    so a sharded trace is never reused outside its mesh."""
    import jax

    from repro.dist import constraint
    from repro.kernels.fleet_score.fleet_score import (fleet_score,
                                                       score_rows_jnp)
    kw = dict(z_threshold=z_threshold, slowdown_floor=slowdown_floor,
              mad_floor_frac=mad_floor_frac, n_valid=n_valid)

    if backend == "jax":
        def run(mats):
            mats = constraint(mats, None, None, "fleet_node")
            return score_rows_jnp(mats, dirs, st_j, **kw)
    else:
        def run(mats):
            return fleet_score(mats, dirs, st_j, **kw)
    return jax.jit(run)


def score_rows(
    mats: np.ndarray,
    dirs: Sequence[float],
    st_j: Optional[int],
    *,
    z_threshold: float = 3.0,
    slowdown_floor: float = 0.025,
    mad_floor_frac: float = 0.01,
    backend: str = "numpy",
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Score R history rows of M metrics over N nodes in one fused pass.

    Returns ``(dev, rel, contrib)``: (R, M, N) bool verdicts, (R, N)
    float32 step-time relative excess, (R, N) float32 deviation-masked
    contribution. See ``ref.score_rows_ref`` for exact semantics.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown fleet_score backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    mats = np.ascontiguousarray(mats, dtype=np.float32)
    assert mats.ndim == 3, mats.shape
    if backend == "numpy":
        return score_rows_ref(
            mats, dirs, st_j, z_threshold=z_threshold,
            slowdown_floor=slowdown_floor, mad_floor_frac=mad_floor_frac)

    from repro.dist import current
    n = mats.shape[2]
    n_valid = None
    if backend == "pallas" and n % _LANE:
        pad = _LANE - n % _LANE
        mats = np.pad(mats, ((0, 0), (0, 0), (0, pad)), mode="constant",
                      constant_values=np.float32(np.nan))
        n_valid = n
    tracing.count("fleet_score.bytes_in", mats.nbytes)
    with tracing.span("fleet_score.call"):
        fn = _compiled(backend, mats.shape, tuple(float(v) for v in dirs),
                       None if st_j is None else int(st_j),
                       float(z_threshold), float(slowdown_floor),
                       float(mad_floor_frac), n_valid, current())
        with tracing.span("fleet_score.dispatch"):
            outs = fn(mats)
        with tracing.span("fleet_score.fetch"):
            dev, rel, contrib = (np.asarray(o) for o in outs)
            out = (dev[..., :n] > 0, rel[:, 0, :n], contrib[:, 0, :n])
    tracing.count("fleet_score.bytes_out",
                  dev.nbytes + rel.nbytes + contrib.nbytes)
    return out


__all__ = ["BACKENDS", "score_rows"]
