"""Radix/hash partition — the shuffle primitive of sort-merge join.

This is the analytics data-plane hot spot (the paper's Fig. 3 "shuffle data
records with the same keys to the same nodes"), TPU-adapted as lane-dense
Pallas kernels. Every id / key column is laid out as ``(rows, 128)`` — one
row of 128 ids per vector lane row — and blocked ``(block // 128, 128)``,
so every block is aligned to the (8, 128) tiling Mosaic requires. The
wrappers pad any ``n > 0`` to a whole block (sentinel id
``num_partitions`` for ids, which lands outside the returned buckets) and
slice the padding off again, so callers never see the block size.

  1. ``partition_histogram`` — per-bucket counts. Each 128-id row is
     compared against a ``(buckets, 128)`` iota (bucket index on sublanes),
     and the one-hot is summed into a resident ``(buckets, 128)``
     accumulator across the sequential grid.
  2. ``partition_destinations`` — the stable grouping: each row's one-hot
     times an upper-triangular ones matrix (one MXU matmul) gives every
     id's inclusive rank within its bucket in that row; a per-bucket
     running count carried across rows and grid steps makes the rank
     global. ``dest = offsets[id] + rank`` is the id's slot in the grouped
     order; ``partition_scatter`` lets XLA apply that permutation, so no
     output is held in VMEM.
  3. ``fused_probe`` — the pipelined join's bucket primitive: the (small)
     build side sits in SMEM and the probe block is compared against one
     build key per loop step.

Validated against ``ref.partition_histogram_ref``,
``ref.partition_scatter_ref`` (stable grouping) and ``ref.fused_probe_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES          # ids in one (8, 128) int32 vreg

_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_rows(col: jax.Array, block: int, fill) -> tuple[jax.Array, int]:
    """Pad a 1-D column to a whole number of blocks (``fill`` in the tail)
    and lay it out ``(rows, 128)``. Returns the 2-D column and the block's
    row count (a multiple of 8). ``block`` is rounded up to whole tiles and
    shrunk to the padded column for small inputs."""
    n = col.shape[0]
    block = min(_round_up(block, TILE), _round_up(n, TILE))
    n_pad = _round_up(n, block)
    col = jnp.pad(col, (0, n_pad - n), constant_values=fill)
    return col.reshape(n_pad // LANES, LANES), block // LANES


def _hist_kernel(ids_ref, out_ref, *, rows: int, buckets: int):
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    iota = jax.lax.broadcasted_iota(jnp.int32, (buckets, LANES), 0)

    def row(r, acc):
        ids = ids_ref[pl.ds(r, 1), :]                          # (1, 128)
        return acc + (ids == iota).astype(jnp.int32)

    out_ref[...] += jax.lax.fori_loop(
        0, rows, row, jnp.zeros((buckets, LANES), jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_partitions", "block",
                                             "interpret"))
def partition_histogram(part_ids: jax.Array, num_partitions: int,
                        block: int = 16384,
                        interpret: bool = False) -> jax.Array:
    """part_ids: (N,) ids in ``[0, num_partitions)`` -> (P,) int32 counts."""
    buckets = _round_up(num_partitions, SUBLANES)
    ids, rows = _lane_rows(part_ids.astype(jnp.int32), block, num_partitions)
    counts = pl.pallas_call(
        functools.partial(_hist_kernel, rows=rows, buckets=buckets),
        grid=(ids.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((buckets, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((buckets, LANES), jnp.int32),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(ids)
    return jnp.sum(counts, axis=1)[:num_partitions]


def _rank_kernel(ids_ref, tri_ref, rank_ref, seen_ref, *, rows: int,
                 buckets: int):
    @pl.when(pl.program_id(0) == 0)
    def _():
        seen_ref[...] = jnp.zeros_like(seen_ref)

    iota = jax.lax.broadcasted_iota(jnp.int32, (buckets, LANES), 0)
    tri = tri_ref[...]

    def row(r, seen):
        ids = ids_ref[pl.ds(r, 1), :]                          # (1, 128)
        hit = ids == iota                                      # (B, 128)
        # [inclusive per-bucket prefix count | row total], exact in f32
        cum = jnp.dot(hit.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
        rank = jnp.where(hit, seen + cum[:, :LANES], 0)
        rank_ref[pl.ds(r, 1), :] = jnp.sum(rank, axis=0, keepdims=True) - 1
        return seen + cum[:, LANES:]

    seen_ref[...] = jax.lax.fori_loop(0, rows, row, seen_ref[...])


@functools.partial(jax.jit, static_argnames=("num_partitions", "block",
                                             "interpret"))
def partition_destinations(part_ids: jax.Array, num_partitions: int,
                           block: int = 16384, interpret: bool = False):
    """Stable grouping as destinations: ``(dest, offsets)`` where
    ``dest[i]`` is row ``i``'s position when rows are grouped by id
    (original order within a bucket) and ``offsets`` (P,) is each bucket's
    exclusive start."""
    n = part_ids.shape[0]
    part_ids = part_ids.astype(jnp.int32)
    buckets = _round_up(num_partitions, SUBLANES)
    ids, rows = _lane_rows(part_ids, block, num_partitions)
    j = jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 1)
    tri = jnp.logical_or(j <= i, i >= LANES).astype(jnp.bfloat16)
    rank, seen = pl.pallas_call(
        functools.partial(_rank_kernel, rows=rows, buckets=buckets),
        grid=(ids.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((LANES, 2 * LANES), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((buckets, LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(ids.shape, jnp.int32),
                   jax.ShapeDtypeStruct((buckets, LANES), jnp.int32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(ids, tri)
    counts = seen[:num_partitions, 0]
    offsets = jnp.cumsum(counts) - counts
    return offsets[part_ids] + rank.reshape(-1)[:n], offsets


@functools.partial(jax.jit, static_argnames=("num_partitions", "block",
                                             "interpret"))
def partition_scatter(rows: jax.Array, part_ids: jax.Array,
                      num_partitions: int, block: int = 16384,
                      interpret: bool = False):
    """Stable grouping of rows by partition id.

    rows: (N, D); part_ids: (N,). Returns (out_rows, offsets) matching
    ``ref.partition_scatter_ref``.
    """
    dest, offsets = partition_destinations(part_ids, num_partitions,
                                           block=block, interpret=interpret)
    out = jnp.zeros_like(rows).at[dest].set(rows, unique_indices=True)
    return out, offsets


def _fused_probe_kernel(bk_ref, bc_ref, pk_ref, v0_ref, v1_ref, grp_ref,
                        wgt_ref, *, build_rows: int, num_groups: int):
    pk = pk_ref[...]

    # build keys are unique among valid rows (join contract) and invalid
    # (padding) rows carry category -1, so the max over matching build rows
    # is the one real match's category, or -1 when there is none
    def build_row(j, cat):
        return jnp.where(pk == bk_ref[j], jnp.maximum(cat, bc_ref[j]), cat)

    cat = jax.lax.fori_loop(0, build_rows, build_row,
                            jnp.full(pk.shape, -1, jnp.int32))
    found = cat >= 0
    grp_ref[...] = jnp.where(found, cat, 0) % num_groups
    wgt_ref[...] = jnp.where(found, v0_ref[...] * v1_ref[...],
                             jnp.float32(0.0))


@functools.partial(jax.jit, static_argnames=("num_groups", "block",
                                             "interpret"))
def fused_probe(probe_keys: jax.Array, v0: jax.Array, v1: jax.Array,
                build_keys: jax.Array, build_cat: jax.Array,
                build_valid: jax.Array, num_groups: int,
                block: int = 8192, interpret: bool = False):
    """Fused partition+probe over one join bucket.

    probe_keys/v0/v1: (N,) probe-side columns; build_keys/build_cat/
    build_valid: (M,) build-side columns (``build_valid`` masks padding
    rows, ``build_cat`` is non-negative). The build side rides along in
    SMEM for every grid step — callers gate on M so it stays small.
    Returns ``(group, weight)`` aligned with probe rows: non-matching rows
    get group 0 / weight 0, the same null encoding as the unfused
    join → where() → mod pipeline.
    """
    n = probe_keys.shape[0]
    pk, rows = _lane_rows(probe_keys.astype(jnp.int32), block, 0)
    v0, _ = _lane_rows(v0.astype(jnp.float32), block, 0)
    v1, _ = _lane_rows(v1.astype(jnp.float32), block, 0)
    bc = jnp.where(build_valid != 0, build_cat.astype(jnp.int32), -1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    probe = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    grp, wgt = pl.pallas_call(
        functools.partial(_fused_probe_kernel,
                          build_rows=build_keys.shape[0],
                          num_groups=num_groups),
        grid=(pk.shape[0] // rows,),
        in_specs=[smem, smem, probe, probe, probe],
        out_specs=[probe, probe],
        out_shape=[jax.ShapeDtypeStruct(pk.shape, jnp.int32),
                   jax.ShapeDtypeStruct(pk.shape, jnp.float32)],
        interpret=interpret,
    )(build_keys.astype(jnp.int32), bc, pk, v0, v1)
    return grp.reshape(-1)[:n], wgt.reshape(-1)[:n]
