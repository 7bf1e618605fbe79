"""Jitted public wrapper for the WKV6 Pallas kernel (model layout)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.wkv6.wkv6 import wkv6_chunked


def wkv6(r, k, v, logw, u, s0, *, chunk: int = 64):
    """Model layout r/k/v/logw (B, S, H, hd), u (H, hd), s0 (B, H, hd, hd)
    -> (y (B, S, H, hd) fp32, state (B, H, hd, hd) fp32)."""
    to_k = lambda x: jnp.moveaxis(x, 1, 2)
    y, s = wkv6_chunked(to_k(r), to_k(k), to_k(v),
                        to_k(logw.astype(jnp.float32)), u,
                        s0.astype(jnp.float32), chunk=chunk)
    return jnp.moveaxis(y, 1, 2), s
