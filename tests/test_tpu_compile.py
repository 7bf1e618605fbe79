"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: what the chip's compiler refuses (an unlowerable primitive,
a block over the scoped-VMEM budget) fails here, with no chip.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file. The persistent compilation cache is
off around these compiles, since an entry written for a described chip
cannot be read back without one."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fleet_score.fleet_score import _fleet_score_call
from repro.kernels.sweep_burn.sweep_burn import _burn_call


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler to describe it with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("n", [16384, 131072])
def test_fleet_score_compiles(one_chip, n):
    """8 detector metrics x n nodes with the step-time row set: the
    verdict row is masked in (no scatter), and the whole-row block fits
    the kernel's scoped-VMEM budget at 131,072 nodes."""
    mats = jax.ShapeDtypeStruct((1, 8, n), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda m: _fleet_score_call(
        m, [1.0] * 8, 0, z_threshold=3.0, slowdown_floor=0.025,
        mad_floor_frac=0.01, n_valid=None, interpret=False), mats)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 8 * n * 4


def test_sweep_burn_compiles(one_chip):
    """The sweep probe at its deployed size: 512^3 f32, 64 iterations."""
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda a, b: _burn_call(
        a, b, iters=64, iters_per_block=8, interpret=False), a, a)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == 512 * 512 * 4
