"""Both Gen-DST Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the chip is *described* (``v5e:2x2``) and the TPU compiler
lowers each kernel for one of its devices, so a tile the Mosaic compiler
refuses, or a block that does not fit VMEM, fails here on the CPU.  The
shapes are the paper's D1 table under the default Gen-DST configuration —
a 360-row subset over the population folded into the column axis
(100 candidates x 22 or 23 columns, the latter with the target) at
B = 256 bins — and D8's 122 columns for the fused generation kernel.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.entropy.kernel import masked_histogram_pallas
from repro.kernels.gen_dst.kernel import fused_delta_fitness_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but not read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("M", [22, 23])
def test_histogram_kernel_compiles_at_gen_dst_population_shape(one_chip, M):
    n, pm, bins = 360, 100 * M, 256
    compiled = jax.jit(
        lambda c, w: masked_histogram_pallas(c, w, bins, interpret=False)
    ).lower(_shape(one_chip, (n, pm), jnp.int32),
            _shape(one_chip, (n,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("P,M,B", [(100, 22, 256), (100, 122, 256)])
def test_fused_generation_kernel_compiles(one_chip, P, M, B):
    compiled = jax.jit(
        lambda *a: fused_delta_fitness_pallas(*a, bins=B, interpret=False)
    ).lower(
        _shape(one_chip, (P, M, B), jnp.float32),
        _shape(one_chip, (P, M), jnp.int32),
        _shape(one_chip, (P, M), jnp.int32),
        _shape(one_chip, (P,), jnp.bool_),
        _shape(one_chip, (P, M), jnp.bool_),
        _shape(one_chip, (), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
