"""Pallas TPU flash-attention (forward): tiled online-softmax causal/full
attention with GQA, adapted for the TPU memory hierarchy.

Tiling: grid (batch, q_heads, S/BQ, T/BK); the innermost (KV) grid dimension
executes sequentially on TPU, so the running max/denominator/accumulator
live in VMEM scratch and persist across KV blocks. Block shapes are
MXU-aligned (multiples of 128 on the contracting/lane dims); the (BQ, BK)
score tile and the (BQ, hd) accumulator bound the VMEM working set
regardless of sequence length — this is the paper-independent hot-spot
kernel for the prefill path.

Causal handling: a KV block entirely in the future is skipped via pl.when
(no MXU work, no VMEM traffic); the diagonal block applies an iota mask.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_interpret

DEFAULT_BQ = 256
DEFAULT_BK = 256
_NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               causal: bool, scale: float, bq: int, bk: int,
               q_offset: int, kv_len: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + qi * bq
    k_start = kj * bk
    # block is live unless every key is in the future of every query
    live = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (BK, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, BK)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len                            # padded keys
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask &= kpos <= qpos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, 0]                           # (BQ,)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                # (BQ, BK)
        l_new = alpha * l_scr[:, 0] + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(kj == nk - 1)
    def _final():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    q_offset: int = 0, kv_len: int = 0):
    """q (B, Hq, S, hd); k/v (B, Hkv, T, hd) -> (B, Hq, S, hd).

    GQA: query head h reads kv head h // (Hq // Hkv). Requires S % block_q
    == 0 and T % block_k == 0 (ops.py pads otherwise); ``kv_len`` is the
    unpadded key count (0 -> T).
    """
    B, Hq, S, hd = q.shape
    _, Hkv, T, _ = k.shape
    G = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    scale = 1.0 / math.sqrt(hd)
    grid = (B, Hq, S // bq, T // bk)

    kernel = functools.partial(_fa_kernel, causal=causal, scale=scale,
                               bq=bq, bk=bk, q_offset=q_offset,
                               kv_len=kv_len or T)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),     # running max
            pltpu.VMEM((bq, 128), jnp.float32),     # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),      # output accumulator
        ],
        interpret=pallas_interpret(),
    )(q, k, v)
