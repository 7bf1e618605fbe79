"""Jitted public wrapper around the flash-attention Pallas kernel.

``attention(q, k, v)`` takes the model's (B, S, H, hd) layout, handles
padding to block multiples, and differentiates via a custom VJP whose
backward recomputes attention with the XLA reference (the standard
recompute-backward pairing for a forward-optimized kernel)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attn(q, k, v, causal, block_q, block_k):
    qp, S = _pad_to(q, 2, block_q)
    kp, T = _pad_to(k, 2, block_k)
    vp, _ = _pad_to(v, 2, block_k)
    out = flash_attention(qp, kp, vp, causal=causal, block_q=block_q,
                          block_k=block_k, kv_len=T)
    return out[:, :, :S]


def _fwd(q, k, v, causal, block_q, block_k):
    return _attn(q, k, v, causal, block_q, block_k), (q, k, v)


def _bwd(causal, block_q, block_k, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: attention_ref(q, k, v, causal=causal), q, k, v)
    return vjp(g)


_attn.defvjp(_fwd, _bwd)


def attention(q, k, v, *, causal: bool = True, block_q: int = 256,
              block_k: int = 256):
    """Model layout q (B, S, Hq, hd), k/v (B, T, Hkv, hd) -> (B, S, Hq, hd).

    The kernel is compiled on an accelerator and interpreted on the CPU
    backend."""
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    out = _attn(qt, kt, vt, causal, block_q, block_k)
    return jnp.moveaxis(out, 1, 2)
