"""Distributed-optimization collectives: gradient compression.

Cross-pod (DCN) gradient all-reduce is the dominant multi-pod cost for big
models; ``compressed_allreduce`` implements an int8 ring-style all-reduce as
all_to_all(int8) -> local dequant-sum -> all_gather(int8), cutting wire bytes
~4x vs fp32 (2x vs bf16) at the cost of one requantization. Used inside
``shard_map`` over the pod/data axis when
``OptimizerConfig.grad_compression`` is enabled; validated against
``lax.psum`` in tests (quantization-bounded error).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size


def _quantize(x: jax.Array, bits: int = 8):
    lim = float(2 ** (bits - 1) - 1)
    absmax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    scale = absmax / lim
    q = jnp.clip(jnp.round(x / scale), -lim, lim).astype(jnp.int8)
    return q, scale


def compressed_allreduce(x: jax.Array, axis_name: str,
                         bits: int = 8) -> jax.Array:
    """int8-wire all-reduce along ``axis_name`` (call inside shard_map).

    x: identical-shape fp array on each shard. Returns sum over shards.
    """
    n = axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)

    q, scale = _quantize(chunks, bits)
    # reduce-scatter phase: shard i receives chunk i from every peer
    gathered = jax.lax.all_to_all(q[:, None], axis_name, split_axis=0,
                                  concat_axis=1)          # (1, n, chunk)
    scales = jax.lax.all_gather(scale, axis_name)         # (n,)
    partial_sum = jnp.sum(
        gathered[0].astype(jnp.float32) * scales[:, None], axis=0)

    # all-gather phase: requantize the reduced chunk, share with all peers
    q2, scale2 = _quantize(partial_sum, bits)
    all_q = jax.lax.all_gather(q2, axis_name)              # (n, chunk)
    all_s = jax.lax.all_gather(scale2, axis_name)          # (n,)
    total = (all_q.astype(jnp.float32) * all_s[:, None]).reshape(-1)
    return total[: x.size].reshape(x.shape).astype(x.dtype)


def make_compressed_grad_allreduce(mesh: Mesh, axis: str = "pod",
                                   bits: int = 8):
    """Returns fn(grads_pytree) -> mean-reduced over ``axis`` with int8 wire.

    Grads must be replicated (or unsharded) along ``axis``; other axes pass
    through unchanged.
    """

    def one(g):
        spec = P()  # fully addressed inside; shard_map over `axis` only

        @partial(shard_map, mesh=mesh, in_specs=P(*([None] * g.ndim)),
                 out_specs=P(*([None] * g.ndim)), check_vma=False)
        def _ar(local):
            summed = compressed_allreduce(local, axis, bits)
            return summed / axis_size(axis)

        return _ar(g)

    return lambda grads: jax.tree.map(one, grads)
