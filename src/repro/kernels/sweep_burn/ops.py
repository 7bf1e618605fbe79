"""Public wrapper: timed sustained-throughput measurement + a local-JAX
SweepBackend so the offline sweep (§5.2) runs for real on whatever
accelerator hosts this process — the deployable counterpart of the
simulator's probe."""
from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sweep import SweepReference
from repro.kernels.sweep_burn.sweep_burn import burn, burn_flops


def measure_tflops(m: int = 512, k: int = 512, iters: int = 64,
                   repeats: int = 3, seed: int = 0) -> float:
    """Median sustained TFLOP/s of the burn chain on the local device."""
    key = jax.random.key(seed)
    a = jax.random.normal(key, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, k), jnp.float32)
    f = jax.jit(lambda a, b: burn(a, b, iters=iters))
    f(a, b).block_until_ready()                   # compile/warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        f(a, b).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return burn_flops(m, k, iters) / np.median(ts) / 1e12


class LocalJaxSweepBackend:
    """SweepBackend over the local JAX device(s): compute probes run the
    Pallas burn kernel; bandwidth probes time a device round-trip copy.
    Used by examples/node_sweep_demo.py."""

    def __init__(self, reference: Optional[SweepReference] = None):
        self._ref = reference

    def device_count(self, node_id: int) -> int:
        return jax.local_device_count()

    def compute_probe(self, node_id: int, device: int,
                      seconds: float) -> float:
        # whole kernel blocks of 8 chained products, 8 to 64 of them
        iters = 8 * max(1, min(int(seconds), 64) // 8)
        return measure_tflops(iters=iters, seed=device)

    def intra_bw_probe(self, node_id: int, dev_a: int, dev_b: int) -> float:
        x = jnp.ones((4 << 20,), jnp.float32)      # 16 MB
        f = jax.jit(lambda x: x + 1)
        f(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(4):
            x = f(x)
        x.block_until_ready()
        dt = time.perf_counter() - t0
        return 4 * 2 * x.nbytes / dt / 1e9

    def multi_node_probe(self, node_ids: Sequence[int],
                         steps: int) -> np.ndarray:
        # single-host stand-in: time a psum-shaped reduction
        x = jnp.ones((1 << 20,), jnp.float32)
        f = jax.jit(lambda x: jnp.sum(x) + x)
        f(x).block_until_ready()
        ts = []
        for _ in range(min(steps, 10)):
            t0 = time.perf_counter()
            f(x).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return np.asarray(ts)

    def reference(self) -> SweepReference:
        if self._ref is None:
            # self-calibrate: current device defines "healthy"
            tf = measure_tflops()
            bw = self.intra_bw_probe(0, 0, 1)
            st = float(np.median(self.multi_node_probe([0, 1], 5)))
            self._ref = SweepReference(tf, bw, st)
        return self._ref
