"""Compile rehearsals of the query-path Pallas kernels, and of the hash
join's build and probe loops, for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: compiling for
a described ``v5e:2x2`` topology raises what the chip's compiler would
(block shapes off the (8, 128) tiling, fast memory over budget, ops Mosaic
cannot lower) at real widths, in about a second per kernel and no chip
time. Nothing runs, so results are checked elsewhere (``test_kernels.py``
in interpret mode, ``chip_smoke.py`` on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off around these compiles
(an entry written for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels import partition

IDS = 1 << 23
PROBE_ROWS = 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip):
    """``compile(fn, *shapes)`` -> the executable compiled for one v5e
    chip, with the persistent compilation cache off around it."""
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


CASES = {
    "histogram_32": (lambda ids: partition.partition_histogram(ids, 32),
                     [((IDS,), jnp.int32)]),
    "histogram_512": (lambda ids: partition.partition_histogram(ids, 512),
                      [((IDS,), jnp.int32)]),
    # 32 buckets + the sentinel bucket: the destinations kernel and the
    # XLA scatter that inverts them, as the shuffle's grouping runs them
    "grouping_33": (lambda ids: kops._grouping_pallas(ids, 32),
                    [((IDS,), jnp.int32)]),
    "fused_probe": (
        lambda pk, v0, v1, bk, bc, bv: partition.fused_probe(
            pk, v0, v1, bk, bc, bv, 64),
        [((PROBE_ROWS,), jnp.int32), ((PROBE_ROWS,), jnp.float32),
         ((PROBE_ROWS,), jnp.float32)]
        + [((kops.FUSED_VMEM_ROWS,), jnp.int32)] * 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_query_path_kernel_compiles_for_v5e(case, compile_for_chip):
    fn, shapes = CASES[case]
    compiled = compile_for_chip(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


# the broadcast hash join at SF10: the item table's 102,000 keys (a
# 2^19-slot table) and one fact partition of 7.2M probe rows; the probe's
# depth is a traced bound, a while loop on the chip
ITEM_ROWS, FACT_PART_ROWS = 102_000, 7_200_248
HASH_CASES = {
    "build": (kops.build_hash_table, [((ITEM_ROWS,), jnp.int32)]),
    "probe": (
        lambda pk, bk, slots, rounds: kops.hash_join_indices(
            pk, bk, kops.HashTable(slots, rounds)),
        [((FACT_PART_ROWS,), jnp.int32), ((ITEM_ROWS,), jnp.int32),
         ((kops._hash_table_size(ITEM_ROWS),), jnp.int32), ((), jnp.int32)]),
}


@pytest.mark.parametrize("case", sorted(HASH_CASES))
def test_hash_join_compiles_for_v5e(case, compile_for_chip):
    fn, shapes = HASH_CASES[case]
    compiled = compile_for_chip(fn, *shapes)
    assert "while" in compiled.as_text()
