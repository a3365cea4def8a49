"""Compile the data plane's Pallas kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered and compiled at a 1 MiB cell width
for one chip of a `v5e:2x2` topology that the installed TPU compiler
describes without a chip attached. This catches what interpret mode
cannot — block shapes the TPU compiler refuses, VMEM overruns — and
checks that a Mosaic kernel (`tpu_custom_call`) is in the program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gf256_matmul import gf256_matmul_planes, gf256_scale_planes
from repro.kernels.xor_reduce import xor_reduce_groups_words, xor_reduce_words

CELL = 1 << 20                 # HDFS RS-6-3-1024k cell, bytes
PLANE_W = CELL // 32           # uint32 words per bit-plane of one cell
WORDS = CELL // 4              # uint32 words of one cell


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


# (kernel, argument shapes and dtypes): a 64-stripe RS(6,3) batch of
# 1 MiB cells — 192 premultiplied helper chunks, a 3x3 parity encode,
# a 4-way fold and 64 three-member fan-in groups
KERNELS = {
    "gf256_scale_planes": (gf256_scale_planes, [
        ((192, 1, 8, 8), jnp.uint32), ((192, 8, PLANE_W), jnp.uint32)]),
    "gf256_matmul_planes": (gf256_matmul_planes, [
        ((3, 3, 8, 8), jnp.uint32), ((3, 8, PLANE_W), jnp.uint32)]),
    "xor_reduce_words": (xor_reduce_words, [((4, WORDS), jnp.uint32)]),
    "xor_reduce_groups_words": (xor_reduce_groups_words, [
        ((64, 3, WORDS), jnp.uint32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
