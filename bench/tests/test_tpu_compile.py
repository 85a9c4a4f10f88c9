"""Compile rehearsals, for a described TPU v5e chip, of the partition
kernels at the shape classes the cells send them: the heavy-hitter
histogram over a whole post-filter partition (an odd row count, padded to
the kernel's block inside), and the grouping program over the partition
padded to its power-of-two class with the join's bucket count. Nothing
runs; the compiler raises what the chip's compiler would.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels import partition

# post-filter rows of one fact partition: SF10 over 4 nodes, SF1 over 8
SF10_PART = 3_600_124
SF1_PART = 180_025
SKETCH_SLOTS = kops.HOT_SKETCH_SLOTS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip):
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        return jax.jit(fn).lower(*args).compile()

    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("rows", [SF10_PART, SF1_PART])
def test_sketch_histogram_compiles(compile_for_chip, rows):
    exe = compile_for_chip(
        lambda ids: partition.partition_histogram(ids, SKETCH_SLOTS),
        ((rows,), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("rows,buckets", [(SF10_PART, 5), (SF10_PART, 10),
                                          (SF1_PART, 1)])
def test_grouping_compiles_at_its_class(compile_for_chip, rows, buckets):
    n_pad = kops._pad_len(rows)
    exe = compile_for_chip(lambda ids: kops._grouping_pallas(ids, buckets),
                           ((n_pad,), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()
