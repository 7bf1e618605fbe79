"""Pallas TPU kernel for the chunked RWKV6 (Finch) WKV recurrence.

The chunked formulation is parallel inside a chunk of C tokens (dense
(C, C) intra-chunk matmuls — MXU work) and sequential across chunks through
the (hd, hd) state. Grid (B, H, S/C): the chunk axis is the innermost grid
dimension, executed sequentially on TPU, so the running state lives in VMEM
scratch and persists chunk-to-chunk — the state never round-trips to HBM
(the same insight flash-attention applies to softmax statistics, applied
here to a linear-attention recurrence).

VMEM working set per program: 4×(C, hd) inputs + (C, C) intra-chunk matrix
+ (hd, hd) state — hardware-aligned for C, hd multiples of 128 (hd=64 runs
under lane packing; fine for the assigned rwkv6-7b head_dim=64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_interpret

_CLAMP = 25.0


def _clip_exp(x):
    return jnp.exp(jnp.clip(x, -_CLAMP, _CLAMP))


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                 y_ref, sout_ref, state, *, chunk: int):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    rr = r_ref[0, 0].astype(jnp.float32)          # (C, hd)
    kk = k_ref[0, 0].astype(jnp.float32)
    vv = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    uf = u_ref[0].astype(jnp.float32)             # (hd,)
    S_prev = state[...]

    clw = jnp.cumsum(lw, axis=0)                  # inclusive
    ecl = clw - lw                                # exclusive
    q_ = rr * _clip_exp(ecl)
    k_ = kk * _clip_exp(-clw)
    A = jax.lax.dot_general(q_, k_, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, C)
    t_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(s_i < t_i, A, 0.0)              # strictly causal
    diag = jnp.sum(rr * uf[None] * kk, axis=1)    # (C,)
    y = jax.lax.dot_general(A, vv, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + diag[:, None] * vv
    y = y + jax.lax.dot_general(q_, S_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    total = clw[-1]                               # (hd,)
    kdecay = kk * _clip_exp(total[None, :] - clw)
    S_new = _clip_exp(total)[:, None] * S_prev + jax.lax.dot_general(
        kdecay, vv, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state[...] = S_new

    @pl.when(c == nc - 1)
    def _final():
        sout_ref[0, 0] = S_new


def wkv6_chunked(r, k, v, logw, u, s0, *, chunk: int = 64):
    """r/k/v (B, H, S, hd); logw (B, H, S, hd) fp32; u (H, hd);
    s0 (B, H, hd, hd) fp32 -> (y (B, H, S, hd) fp32, s (B, H, hd, hd))."""
    B, H, S, hd = r.shape
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    grid = (B, H, S // C)
    blk = lambda b, h, c: (b, h, c, 0)
    sblk = lambda b, h, c: (b, h, 0, 0)

    kernel = functools.partial(_wkv6_kernel, chunk=C)
    y, sout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, C, hd), blk),
            pl.BlockSpec((1, 1, C, hd), blk),
            pl.BlockSpec((1, 1, C, hd), blk),
            pl.BlockSpec((1, 1, C, hd), blk),
            pl.BlockSpec((1, hd), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, hd, hd), sblk),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, hd), blk),
            pl.BlockSpec((1, 1, hd, hd), sblk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=pallas_interpret(),
    )(r, k, v, logw, u, s0)
    return y, sout
