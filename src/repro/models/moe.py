"""Token-choice top-k MoE with sort-based capacity dispatch.

The dispatch buffer ``(B, E, C, D)`` is the "shuffle" of the paper's join
analogy. Two control-plane strategies are expressed purely as sharding
constraints on that buffer (decision node ``moe_strategy``):

  * ``all_to_all`` — experts sharded over ``model``; the dispatch scatter
    redistributes tokens to the expert-owning shards (sort-merge join: both
    sides move by key).
  * ``gather``     — dispatch buffer replicated over ``model``; every shard
    sees all tokens, computes only its local experts, partial outputs
    all-reduce (hash join: broadcast the tokens, keep experts in place).
    Wins when experts are small / token volume is low (paper Fig. 4 regime
    where the broadcast side is cheap).

The sort is per batch row so it never crosses the data-parallel sharding.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.core.config import ModelConfig, MoEConfig
from repro.models.layers import _init
from repro.parallel.sharding import current_rules, logical_shard

Params = dict
Axes = dict


def init_moe(cfg: ModelConfig, key) -> tuple[Params, Axes]:
    assert cfg.moe is not None
    m = cfg.moe
    d, e, f = cfg.d_model, m.num_experts, m.d_expert
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4)
    params: Params = {
        "router": _init(keys[0], (d, e), d ** -0.5, jnp.float32),
        "gate": _init(keys[1], (e, d, f), d ** -0.5, dtype),
        "up": _init(keys[2], (e, d, f), d ** -0.5, dtype),
        "down": _init(keys[3], (e, f, d), f ** -0.5, dtype),
    }
    axes: Axes = {
        "router": ("w_embed", None),
        "gate": ("expert", "w_embed", "mlp"),
        "up": ("expert", "w_embed", "mlp"),
        "down": ("expert", "mlp", "w_embed"),
    }
    return params, axes


def _capacity(tokens: int, m: MoEConfig) -> int:
    c = int(tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(4, -(-c // 4) * 4)          # round up to a multiple of 4


def _dispatch_indices(expert_idx: jax.Array, top_k: int, capacity: int):
    """Per-row sort-based dispatch bookkeeping.

    expert_idx: (B, S, k) chosen experts. Returns (sorted_expert, slot,
    token_src, keep) each (B, S*k): destination (expert, slot) of each
    assignment in sorted order, the source token, and a capacity mask.
    """
    b, s, k = expert_idx.shape
    flat = expert_idx.reshape(b, s * k)
    order = jnp.argsort(flat, axis=-1, stable=True)          # (B, S*k)
    sorted_e = jnp.take_along_axis(flat, order, axis=-1)
    # position within each expert's run
    idx = jnp.arange(s * k)
    boundary = jnp.concatenate(
        [jnp.ones((b, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    run_start = jax.lax.cummax(jnp.where(boundary, idx[None], 0), axis=1)
    slot = idx[None] - run_start
    keep = slot < capacity
    token_src = order // k
    return sorted_e, jnp.minimum(slot, capacity - 1), token_src, order, keep


def _dispatch_row(x_row, p_row, i_row, e: int, cap: int, k: int):
    """Single-sequence dispatch (vmapped over batch: explicit batch indices
    in gather/scatter make GSPMD all-gather the global batch — measured 8 GiB
    per chunk per layer; vmap marks the batch dims so everything stays
    batch-sharded)."""
    flat = i_row.reshape(-1)                                 # (S*k,)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    idx = jnp.arange(flat.shape[0])
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    run_start = jax.lax.cummax(jnp.where(boundary, idx, 0), axis=0)
    slot = jnp.minimum(idx - run_start, cap - 1)
    keep = (idx - run_start) < cap
    token_src = order // k

    gathered = x_row[token_src] * keep[:, None].astype(x_row.dtype)
    buf = jnp.zeros((e, cap, x_row.shape[-1]), x_row.dtype)
    buf = buf.at[sorted_e, slot].add(gathered)
    return buf, (sorted_e, slot, token_src, order, keep)


def _combine_row(out_buf, p_row, bookkeeping, s_chunk: int):
    sorted_e, slot, token_src, order, keep = bookkeeping
    back = out_buf[sorted_e, slot]                           # (S*k, D)
    w = p_row.reshape(-1)[order]
    back = back * (w * keep).astype(back.dtype)[:, None]
    y = jnp.zeros((s_chunk, out_buf.shape[-1]), out_buf.dtype)
    return y.at[token_src].add(back)


def _expert_ffn(params: Params, buf: jax.Array) -> jax.Array:
    """buf: (..., E?, C, D) -> same shape; weights may be pre-sliced."""
    gate = jnp.einsum("becd,edf->becf", buf, params["gate"])
    up = jnp.einsum("becd,edf->becf", buf, params["up"])
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(buf.dtype) * up
    return jnp.einsum("becf,efd->becd", hidden, params["down"])


def moe_shard_map(params: Params, x: jax.Array, cfg: ModelConfig,
                  ) -> tuple[jax.Array, jax.Array]:
    """Explicit all-to-all MoE dispatch (hillclimbed data plane).

    The paper mapping made literal: the dispatch is a sort-merge-join style
    *shuffle* — each model shard routes its own token slice, exchanges
    capacity buffers with the expert-owning shards via two ``all_to_all``s,
    and the combine restores the residual layout. Replaces the
    GSPMD-inferred dispatch (which replicates the token buffers across the
    model axis: 2 orders of magnitude more wire, see EXPERIMENTS.md §Perf).
    """
    rules = current_rules()
    assert rules is not None and rules.mesh is not None
    mesh = rules.mesh
    m = cfg.moe
    tp = int(mesh.shape["model"])
    e_loc = m.num_experts // tp
    seq_sharded = rules.rules.get("seq") is not None
    fsdp_ax = rules.rules.get("w_embed")

    from jax.sharding import PartitionSpec as P

    x_spec = rules.spec("batch", "seq", "embed")
    w_specs = {
        "router": rules.spec("w_embed", None),
        "gate": rules.spec("expert", "w_embed", "mlp_unused"),
        "up": rules.spec("expert", "w_embed", "mlp_unused"),
        "down": rules.spec("expert", "mlp_unused", "w_embed"),
    }

    def body(x_l, wr, wg, wu, wd):
        if fsdp_ax is not None:
            wr = jax.lax.all_gather(wr, fsdp_ax, axis=0, tiled=True)
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=2, tiled=True)
        b = x_l.shape[0]
        if seq_sharded:
            x_m = x_l                      # tokens already sequence-sharded
        else:
            s_loc = x_l.shape[1] // tp
            x_m = jax.lax.dynamic_slice_in_dim(
                x_l, jax.lax.axis_index("model") * s_loc, s_loc, axis=1)
        s_loc = x_m.shape[1]
        cap = _capacity(s_loc, m)

        logits = jnp.einsum("bsd,de->bse", x_m.astype(jnp.float32), wr)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, m.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        frac = jnp.mean(jax.nn.one_hot(top_i[..., 0], m.num_experts,
                                       dtype=jnp.float32), axis=(0, 1))
        aux_terms = jax.lax.pmean(
            jnp.stack([frac, jnp.mean(probs, axis=(0, 1))]), "model")
        aux = m.num_experts * jnp.sum(aux_terms[0] * aux_terms[1])

        sorted_e, slot, token_src, order, keep = _dispatch_indices(
            top_i, m.top_k, cap)
        bidx = jnp.arange(b)[:, None]
        gathered = x_m[bidx, token_src]
        gathered = gathered * keep[..., None].astype(gathered.dtype)
        buf = jnp.zeros((b, m.num_experts, cap, x_l.shape[-1]), x_l.dtype)
        buf = buf.at[bidx, sorted_e, slot].add(gathered)

        # shuffle: (tp_dest, B, E_loc, C, D) -> peers (sort-merge join move)
        send = jnp.moveaxis(
            buf.reshape(b, tp, e_loc, cap, -1), 1, 0)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        # local experts over all sources' tokens: fold sources into capacity
        mine = jnp.moveaxis(recv, 0, 2)            # (B, E_loc, tp, C, D)
        mine = mine.reshape(b, e_loc, tp * cap, -1)
        out = _expert_ffn({"gate": wg, "up": wu, "down": wd}, mine)
        # shuffle back
        out = jnp.moveaxis(
            out.reshape(b, e_loc, tp, cap, -1), 2, 0)  # (tp_src,B,E_loc,C,D)
        back = jax.lax.all_to_all(out, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        buf_back = jnp.moveaxis(back, 0, 1).reshape(
            b, m.num_experts, cap, -1)

        y_rows = buf_back[bidx, sorted_e, slot]
        w = jnp.take_along_axis(top_p.reshape(b, -1), order, axis=-1)
        y_rows = y_rows * (w * keep).astype(y_rows.dtype)[..., None]
        y = jnp.zeros_like(x_m)
        y = y.at[bidx, token_src].add(y_rows)
        if not seq_sharded:
            y = jax.lax.all_gather(y, "model", axis=1, tiled=True)
        return y, aux

    shard_fn = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, w_specs["router"], w_specs["gate"],
                  w_specs["up"], w_specs["down"]),
        out_specs=(x_spec, P()),
        check_vma=False)
    return shard_fn(x, params["router"], params["gate"], params["up"],
                    params["down"])


def moe_shard_map_local(params: Params, x: jax.Array, cfg: ModelConfig,
                        ) -> tuple[jax.Array, jax.Array]:
    """pure_dp MoE: batch is sharded over the whole mesh, experts are
    data-local — the only wire is the internal ZeRO weight gather. Runs in
    shard_map because the partitioner mis-handles the (even batched)
    dispatch scatter's transpose (measured 8 GiB gathers per chunk)."""
    rules = current_rules()
    assert rules is not None and rules.mesh is not None
    mesh = rules.mesh
    m = cfg.moe
    fsdp_ax = rules.rules.get("w_embed")
    from jax.sharding import PartitionSpec as P

    x_spec = rules.spec("batch", "seq", "embed")
    w_specs = (rules.spec("w_embed", None),
               rules.spec(None, "w_embed", None),
               rules.spec(None, "w_embed", None),
               rules.spec(None, None, "w_embed"))

    def body(x_l, wr, wg, wu, wd):
        if fsdp_ax is not None:
            wr = jax.lax.all_gather(wr, fsdp_ax, axis=0, tiled=True)
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=2, tiled=True)
        b, s_loc, d = x_l.shape
        cap = _capacity(s_loc, m)
        logits = jnp.einsum("bsd,de->bse", x_l.astype(jnp.float32), wr)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, m.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        frac = jnp.mean(jax.nn.one_hot(top_i[..., 0], m.num_experts,
                                       dtype=jnp.float32), axis=(0, 1))
        stats = jax.lax.pmean(
            jnp.stack([frac, jnp.mean(probs, axis=(0, 1))]),
            tuple(mesh.shape))
        aux = m.num_experts * jnp.sum(stats[0] * stats[1])

        buf, bookkeeping = jax.vmap(
            lambda xr, pr, ir: _dispatch_row(xr, pr, ir, m.num_experts,
                                             cap, m.top_k))(
            x_l, top_p, top_i)
        out_buf = _expert_ffn({"gate": wg, "up": wu, "down": wd}, buf)
        y = jax.vmap(lambda ob, pr, bk: _combine_row(ob, pr, bk, s_loc))(
            out_buf, top_p, bookkeeping)
        return y, aux

    shard_fn = shard_map(
        body, mesh=mesh, in_specs=(x_spec,) + w_specs,
        out_specs=(x_spec, P()), check_vma=False)
    return shard_fn(x, params["router"], params["gate"], params["up"],
                    params["down"])


def moe(params: Params, x: jax.Array, cfg: ModelConfig,
        s_chunk: int = 1024) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_load_balance_loss)."""
    assert cfg.moe is not None
    rules = current_rules()
    if rules is not None and rules.mesh is not None:
        impl = rules.rules.get("moe_impl")
        if impl == "shard_map_a2a":
            return moe_shard_map(params, x, cfg)
        if impl == "shard_map_local":
            return moe_shard_map_local(params, x, cfg)
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                   # (B,S,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # Switch-style load-balance aux loss (fraction-routed x mean-prob).
    frac = jnp.mean(
        jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

    s_chunk = min(s_chunk, s)
    assert s % s_chunk == 0
    nc = s // s_chunk
    cap = _capacity(s_chunk, m)

    def split(t):  # (B,S,...) -> (nc,B,chunk,...)
        return jnp.moveaxis(t.reshape(b, nc, s_chunk, *t.shape[2:]), 1, 0)

    def one_chunk(args):
        xc, pc, ic = args                   # (B,C,D), (B,C,k), (B,C,k)
        buf, bookkeeping = jax.vmap(
            lambda xr, pr, ir: _dispatch_row(xr, pr, ir, e, cap, k))(
            xc, pc, ic)
        # "expert_act" -> model = all_to_all strategy (tokens move to the
        # expert-owning shards); -> None = gather strategy (tokens broadcast,
        # experts stay put) — the paper's sort-merge vs hash join.
        buf = logical_shard(buf, "batch", "expert_act", "cap", "embed")

        gate = jnp.einsum("becd,edf->becf", buf, params["gate"])
        up = jnp.einsum("becd,edf->becf", buf, params["up"])
        hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(buf.dtype) * up
        hidden = logical_shard(hidden, "batch", "expert_act", "cap", "mlp")
        out_buf = jnp.einsum("becf,efd->becd", hidden, params["down"])
        out_buf = logical_shard(out_buf, "batch", "expert_act", "cap", "embed")

        yc = jax.vmap(
            lambda ob, pr, bk: _combine_row(ob, pr, bk, s_chunk))(
            out_buf, pc, bookkeeping)
        return logical_shard(yc, "batch", "seq", "embed")

    if nc == 1:
        y = one_chunk((x, top_p, top_i))
    else:
        y_chunks = jax.lax.map(one_chunk, (split(x), split(top_p),
                                           split(top_i)))
        y = jnp.moveaxis(y_chunks, 0, 1).reshape(b, s, d)
    return y, aux
