"""Compile the data plane's Pallas kernels, and the programs that keep its
round state on the device, for a described TPU v5e chip.

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

from repro.core.engine import dataplane
from repro.kernels import ops
from repro.kernels.gf256_scale import gf256_scale_rows
from repro.kernels.xor_reduce import xor_reduce_groups_words

CELL = 1 << 20                 # HDFS RS-6-3-1024k cell, bytes
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


# (kernel, argument shapes and dtypes, rows of the premultiply or None):
# the premultiply of one `hdfs-rs-6-3-1024k.repair` batch (48 helper
# chunks of 1 MiB) and of the `fb-warehouse-rs-14-10.repair` batch with
# its double failure (90), then the fold of a 64-stripe RS(6,3) batch
# of 1 MiB cells: 64 three-member fan-in groups
KERNELS = {
    "gf256_scale_rows_hdfs": (gf256_scale_rows, [
        ((48,), jnp.uint8), ((48, CELL), jnp.uint8)], 48),
    "gf256_scale_rows_fb": (gf256_scale_rows, [
        ((90,), jnp.uint8), ((90, CELL), jnp.uint8)], 90),
    "xor_reduce_groups_words": (xor_reduce_groups_words, [
        ((64, 3, WORDS), jnp.uint32)], None),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    """Each kernel is a Mosaic kernel in its program. The premultiply
    reads and writes its (M, 1 MiB) bytes in place: its scratch stays
    below one copy of them, so no packed or relaid-out copy comes back."""
    fn, shapes, rows = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if rows is not None:
        assert compiled.memory_analysis().temp_size_in_bytes < rows * CELL


# The store programs at the shapes of one `hdfs-rs-6-3-1024k.repair`
# batch: 56 rows of 1 MiB, 48 of them premultiplied, and a 24-row round.
ROWS, PRE, ROUND = 56, 48, 24
STORE_PROGRAMS = {
    "grow": (dataplane._grow_device, [((PRE, CELL), jnp.uint8)], (ROWS,)),
    "take": (dataplane._take_device, [
        ((ROWS, CELL // 128, 128), jnp.uint8), ((ROUND,), jnp.int32)],
        (CELL,)),
    "fold_in": (dataplane._fold_in_device, [
        ((ROWS, CELL // 128, 128), jnp.uint8), ((ROUND,), jnp.int32),
        ((ROUND,), jnp.int32), ((ROUND, CELL), jnp.uint8)], ()),
}


@pytest.mark.parametrize("name", sorted(STORE_PROGRAMS))
def test_store_program_compiles_small_for_v5e(name, one_chip,
                                              no_compile_cache):
    """Row by row, each program stays well under a megabyte of code (a
    gather or scatter of whole uint8 rows compiles to 10-22 MB here), and
    the consume-and-accumulate writes the donated store in place."""
    fn, shapes, static = STORE_PROGRAMS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    mem = fn.lower(*args, *static).compile().memory_analysis()
    assert mem.generated_code_size_in_bytes < 1 << 20
    if name == "fold_in":
        assert mem.alias_size_in_bytes == ROWS * CELL


@pytest.mark.parametrize("rows", (48, 90))
def test_stage_stack_compiles_for_v5e(rows, one_chip, no_compile_cache):
    """The stage's stack of 1 MiB rows, handed over one argument each,
    at the premultiply's row counts above, gives the (M, 1 MiB) array the
    premultiply reads (padded to whole tiles of 8 rows). Its scratch (the
    rows' relayout into the 2-D tiles, 1.8x and 2.7x the result here)
    stays under the 4x that one 4-row tile per row would take."""
    args = [jax.ShapeDtypeStruct((CELL,), jnp.uint8, sharding=one_chip)
            ] * rows
    mem = ops._stack_rows.lower(*args).compile().memory_analysis()
    assert rows * CELL <= mem.output_size_in_bytes < (rows + 8) * CELL
    assert mem.temp_size_in_bytes < 4 * rows * CELL
