"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

The *packing* schedule decision (paper Fig. 4e) applied to pods: instead of
stretching data parallelism across the slow cross-pod links (gradient
all-reduce of the full model every step), weights stay pod-local — each pod
owns a contiguous slice of the layer stack — and only microbatch activations
cross pods (one ppermute per pipeline tick). This is the structural answer
to the 72B wire bound recorded in EXPERIMENTS.md §Perf H5.

Implementation: ``shard_map`` manual over ``pod`` only (``axis_names``);
``data``/``model`` stay auto-partitioned by GSPMD inside, so the per-stage
layer stack keeps its TP/FSDP shardings. The schedule is the static GPipe
grid: tick t runs microbatch (t - stage) on each stage, activations move
forward via ``ppermute``; backward is plain AD through the loop (transposed
permutes run the reverse schedule).

Scope: uniform-attention dense archs (block pattern period 1) in train mode,
repeats divisible by the stage count, microbatches >= stages.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core.config import (
    BlockKind,
    ModelConfig,
    OptimizerConfig,
    ParallelConfig,
    ShapeConfig,
)
from repro.models import lm as lm_mod
from repro.models.layers import rmsnorm
from repro.parallel.sharding import ShardingRules, use_rules
from repro.training.losses import chunked_cross_entropy
from repro.training.optimizer import apply_updates

AUX_LOSS_WEIGHT = 0.01


def pp_applicable(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  pc: ParallelConfig) -> bool:
    if "pod" not in getattr(mesh, "shape", {}):
        return False
    stages = int(mesh.shape["pod"])
    pattern, repeats = lm_mod._pattern(cfg)
    return (shape.mode == "train"
            and all(BlockKind(k) == BlockKind.ATTENTION for k in pattern)
            and repeats % stages == 0
            and max(1, pc.microbatches) >= stages)


def make_pp_train_step(cfg: ModelConfig, shape: ShapeConfig,
                       opt_cfg: OptimizerConfig, pc: ParallelConfig,
                       rules: ShardingRules, total_steps: int = 10000,
                       q_chunk: int = 1024):
    """Returns train_step(state, batch). Layer stacks must be sharded over
    ``pod`` on their leading (repeats) axis — use pp_rules()."""
    mesh = rules.mesh
    stages = int(mesh.shape["pod"])
    pattern, repeats = lm_mod._pattern(cfg)
    assert pp_applicable(cfg, shape, mesh, pc)
    mb = max(stages, pc.microbatches)

    def block_specs(template) -> object:
        """P('pod', ...) on every stacked block leaf (auto elsewhere)."""
        return jax.tree.map(lambda x: P("pod"), template)

    def stage_apply(group_params, h, positions):
        """Run this pod's layer slice (scan over R/stages repeats)."""

        def body(carry, layer_params):
            h, aux = carry
            h, aux = lm_mod._apply_block(
                BlockKind.ATTENTION, layer_params, h, positions, cfg,
                128, q_chunk, False, aux)
            return (h, aux), None

        wrapped = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable) \
            if pc.remat != "none" else body
        (h, aux), _ = jax.lax.scan(
            wrapped, (h, jnp.zeros((), jnp.float32)), group_params)
        return h, aux

    def pp_loss(params, tokens_mb, labels_mb):
        """tokens/labels: (M, B_mb, S).

        Embedding and loss run OUTSIDE the manual region (plain GSPMD);
        the shard_map is purely the layer pipeline, and the only cross-
        boundary gradients are dense f32 activation psums (XLA CPU's
        AllReducePromotion crashes on the bf16 / scatter-shaped psums that
        in-region embedding grads would need — micro-repros in tests).
        """
        m_, b_mb, s = tokens_mb.shape
        d = cfg.d_model

        def body(blocks0, h0_all):
            ctx = use_rules(None)   # rules reference the full-auto mesh
            ctx.__enter__()
            stage = jax.lax.axis_index("pod")
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b_mb, s))
            h_recv = jnp.zeros((b_mb, s, d), jnp.dtype(cfg.dtype))
            out_acc = jnp.zeros((mb, b_mb, s, d), jnp.float32)

            perm_fwd = [(i, i + 1) for i in range(stages - 1)]
            for t in range(mb + stages - 1):
                mb_idx = t - stage
                active = jnp.logical_and(mb_idx >= 0, mb_idx < mb)
                safe_idx = jnp.clip(mb_idx, 0, mb - 1)
                h0 = jax.lax.dynamic_index_in_dim(
                    h0_all, safe_idx, axis=0, keepdims=False)
                x_in = jnp.where(stage == 0, h0.astype(h_recv.dtype),
                                 h_recv)
                x_in = jnp.where(active, x_in, jnp.zeros_like(x_in))
                h_out, _ = stage_apply(blocks0, x_in, positions)
                if t >= stages - 1:   # static: last stage can be active
                    take = jnp.logical_and(stage == stages - 1, active)
                    prev = jax.lax.dynamic_index_in_dim(
                        out_acc, safe_idx, axis=0, keepdims=False)
                    upd = jnp.where(take, h_out.astype(jnp.float32), prev)
                    out_acc = jax.lax.dynamic_update_index_in_dim(
                        out_acc, upd, safe_idx, axis=0)
                h_recv = jax.lax.ppermute(h_out, "pod", perm_fwd)

            ctx.__exit__(None, None, None)
            # combine: only the last stage wrote non-zeros; f32 psum is the
            # one all-reduce flavor the CPU backend handles under AD.
            return jax.lax.psum(out_acc, "pod")

        blocks0 = params["blocks"][0]
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(block_specs(blocks0), P()),
            out_specs=P(),
            axis_names={"pod"}, check_vma=True)

        h0_all = jax.vmap(lambda t: lm_mod.embed(params["embed"], t))(
            tokens_mb).astype(jnp.float32)
        h_final = fn(blocks0, h0_all)

        def mb_loss(h, labels):
            h_last = rmsnorm(params["final_norm"], h.astype(cfg.dtype),
                             cfg.norm_eps)
            ce, cnt = chunked_cross_entropy(params["embed"], h_last,
                                            labels, cfg)
            return ce * cnt, cnt
        losses, counts = jax.vmap(mb_loss)(h_final, labels_mb)
        return jnp.sum(losses) / jnp.maximum(jnp.sum(counts), 1.0)

    def train_step(state, batch):
        params = state["params"]
        b = batch["tokens"].shape[0]

        def split(t):
            return t.reshape(mb, b // mb, *t.shape[1:])

        def loss_wrap(p):
            return pp_loss(p, split(batch["tokens"]),
                           split(batch["labels"]))

        loss, grads = jax.value_and_grad(loss_wrap)(params)
        new_params, new_opt, opt_metrics = apply_updates(
            params, grads, state["opt"], opt_cfg, total_steps)
        metrics = dict(opt_metrics)
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics

    train_step.pp_loss = pp_loss   # exposed for tests / fwd-only probes
    return train_step


def pp_rules(rules: ShardingRules) -> ShardingRules:
    """Variant rule set: layer stacks sharded over pod (weights stay
    pod-local); batch stays on data only."""
    new = dict(rules.rules)
    new["layers"] = "pod"
    new["batch"] = "data"
    return ShardingRules(rules.mesh, new)
