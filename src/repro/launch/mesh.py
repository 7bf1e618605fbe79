"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state, so smoke tests keep their single CPU device and
only the dry-run (which sets XLA_FLAGS before any jax import) sees the 512
placeholder devices.

Mesh layout: 16x16 within a pod ("data" x "model": FSDP/DP over data, TP/EP
over model), and a leading "pod" axis (pure DP — cross-pod traffic is only
the gradient all-reduce, riding DCN) for the 2-pod, 512-chip configuration.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # Auto axes: GSPMD propagates shardings from the annotated params
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_cpu_mesh():
    """("data", "model") mesh over every local device — same axis names as
    production, so the identical sharded code paths run in smoke tests.

    "model" stays 1-wide (TP on CPU buys nothing and the manual shard_map
    paths change MoE capacity math); all devices go to "data" so FSDP
    sharding and the JIT all-gather are real whenever the host exposes
    more than one device (CI pins XLA_FLAGS=--xla_force_host_platform_
    device_count=8 for exactly this).
    """
    return _make_mesh((len(jax.devices()), 1), ("data", "model"))
