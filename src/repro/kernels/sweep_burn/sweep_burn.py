"""MXU-aligned sustained-matmul burn kernel — the single-node sweep's
compute probe (§5.2).

Unlike a burn-in correctness test, the probe measures *sustained* matmul
throughput: ``iters`` back-to-back (M, K) @ (K, N) products whose operands
stay resident in VMEM (no HBM traffic after the first load), so the
measured rate is pure MXU + thermal behaviour. Tiles default to 512³ —
multiples of the 128×128 systolic array with a VMEM footprint (3 MB fp32)
that fits comfortably alongside double-buffering.

A data-dependent chain (each product feeds the next through a cheap
rescale) prevents the compiler from collapsing the loop; the scalar
checksum output also serves as a numerical-health check: one flaky MAC
shows up as a checksum mismatch across devices running the same seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_interpret


def _burn_kernel(a_ref, b_ref, o_ref, acc, *, iters_per_block: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = a_ref[...]

    def body(_, x):
        y = jax.lax.dot_general(x, b_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # renormalize so the chain neither explodes nor denorms
        scale = jax.lax.rsqrt(jnp.mean(jnp.square(y)) + 1e-12)
        return y * scale

    acc[...] = jax.lax.fori_loop(0, iters_per_block, body, acc[...])

    @pl.when(i == pl.num_programs(0) - 1)
    def _final():
        o_ref[...] = acc[...]


def burn(a, b, *, iters: int = 64, iters_per_block: int = 8):
    """a (M, K), b (K, N) fp32 -> (M, N) chained product. Compiled on an
    accelerator, interpreted on the CPU backend.

    FLOPs executed = 2 * M * K * N * iters (requires K == N for chaining).
    """
    return _burn_call(a, b, iters=iters, iters_per_block=iters_per_block,
                      interpret=pallas_interpret())


def _burn_call(a, b, *, iters: int, iters_per_block: int, interpret: bool):
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 == N, "burn chain needs square b"
    assert iters % iters_per_block == 0
    grid = (iters // iters_per_block,)
    kernel = functools.partial(_burn_kernel,
                               iters_per_block=iters_per_block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((M, K), lambda i: (0, 0)),
                  pl.BlockSpec((K, N), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((M, N), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((M, N), jnp.float32)],
        interpret=interpret,
    )(a, b)


def burn_flops(M: int, K: int, iters: int) -> float:
    return 2.0 * M * K * K * iters
