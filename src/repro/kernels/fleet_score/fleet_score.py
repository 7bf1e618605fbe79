# guardlint: hot  (fleet-sized arrays live here: float32, no per-node loops)
"""Fused fleet-score kernel: peer-median, MAD, robust-z and threshold
verdicts over ``(R, M, N)`` ring-buffer rows in one pass, float32.

The interesting part is the median. ``jax.lax.sort`` does not lower
inside Pallas TPU kernels and a sort is not shardable anyway, so order
statistics are found by *bisection in the key space*: float32 bit
patterns map through the standard monotonic transform

    u    = bitcast(x, uint32)
    key  = ~u            if sign bit set  (negatives reverse)
           u | 0x8000..  otherwise        (positives above negatives)

into uint32 keys whose integer order equals IEEE-754 total order (NaNs
above +inf, exactly where ``np.partition`` places them). A 32-round
binary search then pins the k-th smallest key: each round counts
``sum(key <= mid)`` along the node axis and halves the interval. The
count is the ONLY cross-node operation — an elementwise compare plus a
sum reduction — which makes the whole scorer a shardable reduction over
a ``repro.dist`` node axis (the counts psum across shards under GSPMD)
and TPU-lowerable inside Pallas (no gather, no sort network).

The recovered order statistic is the exact element bit pattern, so the
median — ``(a + b) / 2`` of the two middle statistics for even N — is
bit-identical to the ``np.partition`` reference in ``ref.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_interpret

# numpy scalars (not jnp 0-d arrays): they inline as jaxpr literals, so
# the Pallas trace captures no constants
_SIGN = np.uint32(0x80000000)
_DEFAULT_SCOPED_VMEM = 16 * 2**20     # Mosaic's scoped-VMEM default


def float_key(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 keys in IEEE total order (NaNs largest)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u & _SIGN != 0, ~u, u | _SIGN)


def key_float(k: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``float_key`` — exact bit pattern round trip."""
    u = jnp.where(k & _SIGN != 0, k ^ _SIGN, ~k)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def kth_smallest_key(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """(..., N) uint32 -> (..., 1): the k-th smallest key per row.

    32 bisection rounds over the key space; the per-round rank count is
    the shardable node-axis reduction."""
    shape = keys.shape[:-1] + (1,)
    target = np.int32(k + 1)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo) >> 1)
        rank = jnp.sum((keys <= mid).astype(jnp.int32), axis=-1,
                       keepdims=True)
        take = rank >= target
        return (jnp.where(take, lo, mid + np.uint32(1)),
                jnp.where(take, mid, hi))

    lo, _ = jax.lax.fori_loop(
        0, 32, body, (jnp.zeros(shape, jnp.uint32),
                      jnp.full(shape, 0xFFFFFFFF, jnp.uint32)))
    return lo


def median_lastdim(x: jnp.ndarray, n_valid: Optional[int] = None
                   ) -> jnp.ndarray:
    """(..., N) -> (..., 1) median, bit-identical to the np.partition
    reference. ``n_valid`` restricts the order statistics to the first
    ``n_valid`` logical elements when the lane dim is padded — pads must
    sort above every real value (use float32 NaN)."""
    n = x.shape[-1] if n_valid is None else int(n_valid)
    keys = float_key(x)
    h = n // 2
    if n % 2:
        return key_float(kth_smallest_key(keys, h))
    a = key_float(kth_smallest_key(keys, h - 1))
    b = key_float(kth_smallest_key(keys, h))
    return (a + b) / 2.0


def score_rows_jnp(
    mats: jnp.ndarray,
    dirs: Union[Sequence[float], jnp.ndarray],
    st_j: Optional[int],
    *,
    z_threshold: float,
    slowdown_floor: float,
    mad_floor_frac: float,
    n_valid: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The fused scorer on (R, M, N) float32 — the single implementation
    shared by the jitted jax backend (full array, shardable) and the
    Pallas kernel (one (1, M, N) block per grid step, ``dirs`` arriving
    as an operand ref so the kernel trace stays constant-free).

    Returns ``(dev, rel, contrib)`` with dev as a float32 0/1 mask
    (uniform tiling on TPU; the ops layer casts to bool) and rel/contrib
    shaped (R, 1, N)."""
    f32 = np.float32
    x = mats.astype(jnp.float32)
    m = x.shape[1]
    d = jnp.asarray(dirs, jnp.float32).reshape(1, m, 1)
    med = median_lastdim(x, n_valid)                       # (R, M, 1)
    diff = x - med
    mad = median_lastdim(jnp.abs(diff), n_valid)
    floor = jnp.maximum(jnp.abs(med) * f32(mad_floor_frac), f32(1e-9))
    scale = jnp.maximum(mad / f32(0.6745), floor)
    z = (diff / scale) * d
    dev = (z > f32(z_threshold)).astype(jnp.float32)
    if st_j is None:
        zero = jnp.zeros((x.shape[0], 1, x.shape[2]), jnp.float32)
        return dev, zero, zero
    xs = x[:, st_j:st_j + 1]                               # (R, 1, N)
    ms = jnp.maximum(med[:, st_j:st_j + 1], f32(1e-9))     # (R, 1, 1)
    rel = xs / ms - f32(1.0)
    sdev = (dev[:, st_j:st_j + 1] > 0) & (rel > f32(slowdown_floor))
    row = jax.lax.broadcasted_iota(jnp.int32, dev.shape, 1) == st_j
    dev = jnp.where(row, sdev.astype(jnp.float32), dev)
    contrib = jnp.where(sdev, rel, f32(0.0))
    return dev, rel, contrib


def _fleet_score_kernel(mats_ref, dirs_ref, dev_ref, rel_ref,
                        contrib_ref, *, st_j, n_valid, z_threshold,
                        slowdown_floor, mad_floor_frac):
    dev, rel, contrib = score_rows_jnp(
        mats_ref[...], dirs_ref[...].reshape(-1), st_j,
        z_threshold=z_threshold, slowdown_floor=slowdown_floor,
        mad_floor_frac=mad_floor_frac, n_valid=n_valid)
    dev_ref[...] = dev
    rel_ref[...] = rel
    contrib_ref[...] = contrib


def fleet_score(
    mats: jnp.ndarray,
    dirs: Sequence[float],
    st_j: Optional[int],
    *,
    z_threshold: float,
    slowdown_floor: float,
    mad_floor_frac: float,
    n_valid: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas entry point: grid over R rows, one fused (M, N) block per
    step resident in VMEM. Compiled on an accelerator, interpreted on the
    CPU backend. The lane dim should be padded to the 128-lane tile with
    float32 NaN and the true node count passed as ``n_valid``."""
    return _fleet_score_call(
        mats, dirs, st_j, z_threshold=z_threshold,
        slowdown_floor=slowdown_floor, mad_floor_frac=mad_floor_frac,
        n_valid=n_valid, interpret=pallas_interpret())


def _vmem_limit_bytes(m: int, n: int) -> int:
    """Scoped-VMEM budget for one (1, M, N) grid step.

    The whole node row must be resident: every bisection round counts
    ranks over all N. The kernel holds the (M, N) input block and the
    (M, N) verdict block plus two (1, N) outputs, each double-buffered,
    and the compiler's scratch for keys and deviations, which stays under
    four more (M, N) blocks. At 8 metrics x 131,072 nodes that is
    2 x 9 MiB of buffers + 16 MiB of scratch = 34 MiB, where the TPU
    compiler asks for 21.5 MiB with one row and 30.5 MiB with several:
    above the 16 MiB default, inside v5e's 128 MiB of VMEM."""
    block = m * n * 4
    buffers = 2 * (2 * block + 2 * n * 4)
    return max(_DEFAULT_SCOPED_VMEM, buffers + 4 * block)


def _fleet_score_call(mats, dirs, st_j, *, z_threshold, slowdown_floor,
                      mad_floor_frac, n_valid, interpret: bool):
    r, m, n = mats.shape
    dirs_arr = np.asarray(dirs, np.float32).reshape(m, 1)
    kernel = functools.partial(
        _fleet_score_kernel,
        st_j=st_j, n_valid=n_valid, z_threshold=float(z_threshold),
        slowdown_floor=float(slowdown_floor),
        mad_floor_frac=float(mad_floor_frac))
    return pl.pallas_call(
        kernel,
        grid=(r,),
        in_specs=[pl.BlockSpec((1, m, n), lambda i: (i, 0, 0)),
                  pl.BlockSpec((m, 1), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, m, n), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, m, n), jnp.float32),
                   jax.ShapeDtypeStruct((r, 1, n), jnp.float32),
                   jax.ShapeDtypeStruct((r, 1, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(m, n)),
        interpret=interpret,
        name="fleet_score",
    )(mats, dirs_arr)


__all__ = ["fleet_score", "float_key", "key_float", "kth_smallest_key",
           "median_lastdim", "score_rows_jnp"]
