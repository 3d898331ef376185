"""Compile the chip path for a described TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend, so the Pallas kernels
and the 4-device ``shard_map`` launch compile here for a ``v5e:2x2``
topology at deployment widths: 8M rows (a 6.7M-row table padded to its
power-of-two size class), 128 to 65,536 groups, 1,000 ranges, batch 16.
Interpret mode cannot see what Mosaic refuses (layouts, tiling, VMEM over
the scoped limit); these compiles can.  Nothing runs, so results and times
come only from the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Code that asks ``jax.default_backend()`` still sees the CPU here, so
the tests pick the Pallas path with ``ops.forced_backend``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import ops

ROWS = 1 << 23
RANGES = 1000
BATCH = 16
HBM_BYTES = 16 * 10 ** 9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, *shapes):
    with ops.forced_backend("pallas"):
        return jax.jit(fn).lower(*shapes).compile()


def _assert_fits(compiled):
    """A Mosaic custom call is in the program, and the program's arguments,
    outputs and temporaries fit one chip's HBM.  (Mosaic itself refuses a
    kernel whose VMEM use exceeds the scoped limit, so a compile that
    returns has stayed inside it.)"""
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total


@pytest.mark.parametrize("n_groups", [512, 128, 8192, 16384, 65536])
def test_segment_aggregate_compiles(one_chip, n_groups):
    f32 = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda v, g, w: ops.segment_aggregate(v, g, n_groups, w), f32, i32, f32)
    _assert_fits(compiled)


@pytest.mark.parametrize("n_groups", [512, 128, 8192, 16384, 65536])
def test_segment_aggregate_batch_compiles(one_chip, n_groups):
    shape = (BATCH, ROWS // BATCH)
    f32 = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda v, g, w: ops.segment_aggregate_batch(v, g, n_groups, w), f32, i32, f32)
    _assert_fits(compiled)


def test_fragment_bitmap_compiles(one_chip):
    prov = jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=one_chip)
    bucket = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda p, b: ops.fragment_bitmap(p, b, RANGES), prov, bucket)
    _assert_fits(compiled)


def test_fragment_bitmap_batch_compiles(one_chip):
    provs = jax.ShapeDtypeStruct((BATCH, ROWS), jnp.bool_, sharding=one_chip)
    bucket = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda p, b: ops.fragment_bitmap_batch(p, b, RANGES), provs, bucket)
    _assert_fits(compiled)


def test_sketch_filter_compiles(one_chip):
    bucket = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    bits = jax.ShapeDtypeStruct((RANGES,), jnp.bool_, sharding=one_chip)
    compiled = _compile(ops.sketch_filter, bucket, bits)
    _assert_fits(compiled)


def test_shard_map_launch_compiles_on_four_chips(topo):
    """The sharded engine's fused launch: (queries, shards, rows) stacked
    arrays split over a 4-device mesh, the batched kernel per device, psum
    across it."""
    from repro.core.shard import _fused_spmd_fn

    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("shards",))
    spec = NamedSharding(mesh, PartitionSpec(None, "shards", None))
    shape = (BATCH, 4, ROWS // 4 // BATCH)
    vals = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=spec)
    gid = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=spec)
    fn = _fused_spmd_fn(mesh)
    with ops.forced_backend("pallas"):
        compiled = fn.lower(vals, gid, vals, 512).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    assert compiled.memory_analysis().argument_size_in_bytes < HBM_BYTES
    # Each device holds one shard's quarter of the stacked rows.
    assert compiled.input_shardings[0][0].shard_shape(shape) == (BATCH, 1, shape[2])
