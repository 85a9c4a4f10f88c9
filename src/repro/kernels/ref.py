"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each kernel in this package is validated against these references in
``tests/test_kernels.py`` across shape/dtype sweeps (interpret mode on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """q,k,v: (B, S, H, hd) (KV already expanded to H heads). fp32 softmax."""
    b, s, h, hd = q.shape
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (hd ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshk->bqhk", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, length: jax.Array) -> jax.Array:
    """q: (B, H, hd); caches: (B, S, K, hd); length: (B,) valid prefix sizes.

    GQA: H = K * G; query head i attends through kv head i // G.
    """
    b, h, hd = q.shape
    _, s, kh, _ = k_cache.shape
    g = h // kh
    k_exp = jnp.repeat(k_cache, g, axis=2)          # (B, S, H, hd)
    v_exp = jnp.repeat(v_cache, g, axis=2)
    scores = jnp.einsum("bhk,bshk->bhs", q, k_exp,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    valid = jnp.arange(s)[None, :] < length[:, None]
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshk->bhk", probs.astype(v_exp.dtype), v_exp)
    return out.astype(q.dtype)


def partition_histogram_ref(part_ids: jax.Array,
                            num_partitions: int) -> jax.Array:
    """part_ids: (N,) int32 -> (P,) counts."""
    return jnp.bincount(part_ids, length=num_partitions).astype(jnp.int32)


def partition_scatter_ref(rows: jax.Array, part_ids: jax.Array,
                          num_partitions: int):
    """Stable grouping of rows by partition id.

    rows: (N, D); returns (out_rows (N, D), offsets (P,)) where
    out_rows[offsets[p] : offsets[p] + counts[p]] are partition p's rows in
    original order.
    """
    order = jnp.argsort(part_ids, stable=True)
    counts = partition_histogram_ref(part_ids, num_partitions)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    return rows[order], offsets


def fused_probe_ref(pk: jax.Array, v0: jax.Array, v1: jax.Array,
                    bk: jax.Array, bc: jax.Array, bv: jax.Array,
                    num_groups: int):
    """Fused probe oracle, dense: the (N, M) equality matrix of probe keys
    against valid build keys (build keys unique among valid rows). Returns
    ``(group, weight)`` per probe row; non-matching rows carry group 0 /
    weight 0."""
    match = jnp.logical_and(pk[:, None] == bk[None, :], bv[None, :] != 0)
    found = jnp.any(match, axis=1)
    cat = jnp.where(found, bc[jnp.argmax(match, axis=1)], 0)
    weight = jnp.where(found, v0 * v1, jnp.float32(0.0))
    return cat % num_groups, weight
