"""Grouped-query attention with strategy-aware sharding annotations.

Three control-plane strategies (picked by decision nodes, see
``repro/parallel/strategies.py``) are expressed purely through logical-axis
rules — the math below is strategy-agnostic:

  * head_tp  — heads sharded over ``model`` (Megatron TP); residual replicated.
  * seq_tp   — residual sequence-sharded over ``model``; KV projections are
               *broadcast* (all-gather) to every shard — the paper's hash-join
               move (ship the small table), used when head counts don't divide
               the model axis.
  * decode   — KV cache sharded along its sequence axis; softmax statistics
               combine across shards (flash-decode, GSPMD-inferred).

The einsum formulation here is the pure-JAX data plane; the Pallas kernels in
``repro/kernels`` implement the same contract for the TPU hot path and are
validated against ``repro/kernels/ref.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.core.config import ModelConfig
from repro.models.layers import _init, apply_rope
from repro.parallel.sharding import current_rules, logical_shard

Params = dict
Axes = dict

NEG_INF = -1e9


def init_attention(cfg: ModelConfig, key) -> tuple[Params, Axes]:
    d, h, k_heads = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4)
    params: Params = {
        "wq": _init(keys[0], (d, h, hd), d ** -0.5, dtype),
        "wk": _init(keys[1], (d, k_heads, hd), d ** -0.5, dtype),
        "wv": _init(keys[2], (d, k_heads, hd), d ** -0.5, dtype),
        "wo": _init(keys[3], (h, hd, d), (h * hd) ** -0.5, dtype),
    }
    axes: Axes = {
        "wq": ("w_embed", "heads", "qkv"),
        "wk": ("w_embed", "kv_heads", "qkv"),
        "wv": ("w_embed", "kv_heads", "qkv"),
        "wo": ("heads", "qkv", "w_embed"),
    }
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((h, hd), dtype)
        params["bk"] = jnp.zeros((k_heads, hd), dtype)
        params["bv"] = jnp.zeros((k_heads, hd), dtype)
        axes["bq"] = ("heads", "qkv")
        axes["bk"] = ("kv_heads", "qkv")
        axes["bv"] = ("kv_heads", "qkv")
    return params, axes


def _project_qkv(params: Params, x: jax.Array, positions: jax.Array,
                 cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       q_chunk: int, causal: bool = True,
                       causal_skip: bool = False) -> jax.Array:
    """Blocked causal attention: O(q_chunk * S) score memory.

    q, k, v: (B, S, H, hd) — KV already expanded to H query heads.
    ``causal_skip`` unrolls the chunk loop with static KV prefixes so the
    strictly-upper-triangle chunk blocks are never computed (~2x fewer
    attention FLOPs at long context; §Perf H2).
    """
    b, s, h, hd = q.shape
    scale = hd ** -0.5
    q_chunk = min(q_chunk, s)
    n_chunks = max(1, s // q_chunk)
    assert s % q_chunk == 0, (s, q_chunk)

    def chunk_out(chunk_id, qb, k_in, v_in):
        scores = jnp.einsum("bchk,bshk->bhcs", qb, k_in,
                            preferred_element_type=jnp.float32)
        scores = scores * scale
        if causal:
            q_idx = chunk_id * q_chunk + jnp.arange(q_chunk)
            kv_idx = jnp.arange(k_in.shape[1])
            mask = q_idx[:, None] >= kv_idx[None, :]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_in.dtype)
        return jnp.einsum("bhcs,bshk->bchk", probs, v_in)

    if causal and causal_skip and n_chunks > 1:
        outs = []
        for ci in range(n_chunks):
            end = (ci + 1) * q_chunk
            qb = q[:, ci * q_chunk: end]
            outs.append(chunk_out(ci, qb, k[:, :end], v[:, :end]))
        return jnp.concatenate(outs, axis=1)

    q_blocks = jnp.moveaxis(q.reshape(b, n_chunks, q_chunk, h, hd), 1, 0)
    out = jax.lax.map(lambda args: chunk_out(args[0], args[1], k, v),
                      (jnp.arange(n_chunks), q_blocks))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def _int8_broadcast(t: jax.Array) -> jax.Array:
    """Force the seq_tp KV broadcast onto an int8 wire (§Perf H2).

    A with_sharding_constraint on the quantized tensor is NOT enough: the
    partitioner may legally all-gather the bf16 producer and re-quantize
    replicated (measured: zero wire saving). shard_map pins the collective:
    quantize shard-locally (scales over head_dim only), all-gather the int8
    payload + fp32 scale sliver explicitly, dequantize after."""
    rules = current_rules()
    if rules is None or rules.mesh is None \
            or rules.rules.get("seq") is None:
        return logical_shard(t, "batch", "kv_seq", "kv_rep", "qkv")
    mesh = rules.mesh
    in_spec = rules.spec("batch", "seq", "kv_rep", "qkv")
    out_spec = rules.spec("batch", "kv_seq", "kv_rep", "qkv")

    @jax.custom_vjp
    def gather_int8(local):
        absmax = jnp.maximum(jnp.max(jnp.abs(local.astype(jnp.float32)),
                                     axis=3, keepdims=True), 1e-9)
        scale = absmax / 127.0
        q = jnp.clip(jnp.round(local.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
        q_full = jax.lax.all_gather(q, "model", axis=1, tiled=True)
        s_full = jax.lax.all_gather(scale.astype(jnp.float32), "model",
                                    axis=1, tiled=True)
        return (q_full.astype(jnp.float32) * s_full).astype(local.dtype)

    # straight-through estimator: round() has zero gradient, so the
    # backward pass is the exact identity-all-gather transpose (bf16
    # reduce-scatter); only fwd + remat-fwd ride the int8 wire.
    def _fwd(local):
        return gather_int8(local), None

    def _bwd(_, g):
        return (jax.lax.psum_scatter(g, "model", scatter_dimension=1,
                                     tiled=True),)

    gather_int8.defvjp(_fwd, _bwd)

    return shard_map(gather_int8, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(t)


def attention(params: Params, x: jax.Array, positions: jax.Array,
              cfg: ModelConfig, q_chunk: int = 1024,
              causal: bool = True) -> jax.Array:
    """Full (train / prefill) attention. x: (B, S, D)."""
    b, s, _ = x.shape
    kh = cfg.num_kv_heads
    g = cfg.num_heads // kh
    hd = cfg.resolved_head_dim
    rules = current_rules()
    kv_compress = bool(rules and rules.rules.get("kv_compress"))
    causal_skip = bool(rules and rules.rules.get("causal_skip"))

    q, k, v = _project_qkv(params, x, positions, cfg)
    q = logical_shard(q, "batch", "seq", "heads", "qkv")
    # Hash-join move: under seq_tp the small (num_kv_heads-wide) KV tensors
    # are broadcast (all-gathered) to every shard *before* the g-fold expand.
    if kv_compress:
        k = _int8_broadcast(k)
        v = _int8_broadcast(v)
    else:
        k = logical_shard(k, "batch", "kv_seq", "kv_rep", "qkv")
        v = logical_shard(v, "batch", "kv_seq", "kv_rep", "qkv")
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    k = logical_shard(k, "batch", "kv_seq", "heads", "qkv")
    v = logical_shard(v, "batch", "kv_seq", "heads", "qkv")

    out = _chunked_attention(q, k, v, q_chunk=q_chunk, causal=causal,
                             causal_skip=causal_skip)
    out = logical_shard(out, "batch", "seq", "heads", "qkv")
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return logical_shard(y, "batch", "seq", "embed")


def prefill_attention(params: Params, cache: tuple[jax.Array, jax.Array],
                      x: jax.Array, positions: jax.Array, cfg: ModelConfig,
                      q_chunk: int = 1024,
                      ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Process a whole prompt and populate the KV cache. x: (B, S, D)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, positions, cfg)
    k_cache, v_cache = cache
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, 0, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, 0, 0, 0))
    g = cfg.num_heads // cfg.num_kv_heads
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    out = _chunked_attention(q, k, v, q_chunk=min(q_chunk, s), causal=True)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return logical_shard(y, "batch", "seq", "embed"), (k_cache, v_cache)


# -- Decode path ---------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int,
                  dtype=None) -> tuple[jax.Array, jax.Array]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = jnp.zeros((batch, max_seq, kh, hd), dtype)
    v = jnp.zeros((batch, max_seq, kh, hd), dtype)
    return k, v


def cache_axes() -> tuple[str, ...]:
    return ("batch", "cache_seq", "kv_heads", "qkv")


def decode_attention(params: Params, cache: tuple[jax.Array, jax.Array],
                     x: jax.Array, positions: jax.Array, cfg: ModelConfig,
                     ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One decode step. x: (B, 1, D); positions: (B,) current index.

    The KV cache is sharded along ``cache_seq``; the softmax over the sharded
    sequence axis lowers to per-shard partials + a tiny all-reduce
    (flash-decode, inferred by GSPMD).
    """
    b, one, _ = x.shape
    assert one == 1
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // kh
    k_cache, v_cache = cache
    max_seq = k_cache.shape[1]

    q, k_new, v_new = _project_qkv(params, x, positions[:, None], cfg)
    batch_idx = jnp.arange(b)
    k_cache = k_cache.at[batch_idx, positions].set(k_new[:, 0])
    v_cache = v_cache.at[batch_idx, positions].set(v_new[:, 0])
    k_cache = logical_shard(k_cache, *cache_axes())
    v_cache = logical_shard(v_cache, *cache_axes())

    q = q.reshape(b, kh, g, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", q, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores * (hd ** -0.5)
    valid = jnp.arange(max_seq)[None, :] <= positions[:, None]   # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v_cache)
    out = out.reshape(b, 1, cfg.num_heads, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return logical_shard(y, "batch", "seq", "embed"), (k_cache, v_cache)
