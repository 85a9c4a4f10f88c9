"""Multi-device equivalence tests for the explicit shard_map data planes
(MoE all-to-all dispatch, int8 KV broadcast, sLSTM scan). These need >1
device, so they run in subprocesses with forced host devices."""

import os
import subprocess
import sys
import textwrap

import pytest

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
if "JAX_PLATFORMS" in os.environ:   # keep the backend pin: plugin
    ENV["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]  # probing can hang


def run(script: str):
    result = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                            capture_output=True, text=True, timeout=600,
                            env=ENV)
    assert result.returncode == 0, result.stderr[-3000:]
    assert "OK" in result.stdout


@pytest.mark.slow
def test_moe_shard_map_matches_reference():
    run("""
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.moe import init_moe, moe, moe_shard_map
    from jax import set_mesh
    from repro.parallel.sharding import ShardingRules, use_rules

    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=8, top_k=2, capacity_factor=8.0))
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    params, _ = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)
    y_ref, _ = moe(params, x, cfg)
    rules = ShardingRules(mesh, {"batch": "data", "seq": None,
                                 "embed": None, "expert": "model",
                                 "w_embed": None,
                                 "moe_impl": "shard_map_a2a"})
    with set_mesh(mesh), use_rules(rules):
        y, _ = jax.jit(lambda p, x: moe_shard_map(p, x, cfg))(params, x)
        # gradients flow
        g = jax.jit(jax.grad(
            lambda p, x: jnp.sum(moe_shard_map(p, x, cfg)[0] ** 2)))(
            params, x)
    err = float(jnp.max(jnp.abs(y_ref - y)))
    assert err < 1e-4, err
    gn = float(jnp.linalg.norm(g["gate"]))
    assert gn > 0, "expert grads must flow through the a2a"
    print("OK", err, gn)
    """)


@pytest.mark.slow
def test_int8_kv_broadcast_close_and_differentiable():
    run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.attention import init_attention, attention
    from jax import set_mesh
    from repro.parallel.sharding import ShardingRules, use_rules

    cfg = get_config("qwen1.5-4b", smoke=True)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    params, _ = init_attention(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                          jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    base = {"batch": "data", "seq": "model", "kv_seq": None,
            "kv_rep": None, "heads": None, "qkv": None, "embed": None,
            "mlp_seq": None, "w_embed": None}

    def run_case(extra):
        rules = ShardingRules(mesh, {**base, **extra})
        with set_mesh(mesh), use_rules(rules):
            out = jax.jit(lambda p, x: attention(p, x, pos, cfg,
                                                 q_chunk=8))(params, x)
            g = jax.jit(jax.grad(lambda p, x: jnp.sum(
                attention(p, x, pos, cfg, q_chunk=8) ** 2)))(params, x)
        return out, g

    o0, g0 = run_case({})
    o1, g1 = run_case({"kv_compress": True, "causal_skip": True})
    err = float(jnp.max(jnp.abs(o0 - o1)))
    assert err < 0.05, err
    for k in ("wk", "wv"):
        n0 = float(jnp.linalg.norm(g0[k]))
        n1 = float(jnp.linalg.norm(g1[k]))
        assert abs(n0 - n1) / n0 < 0.05, (k, n0, n1)
    print("OK", err)
    """)


@pytest.mark.slow
def test_slstm_shard_map_matches_unsharded():
    run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.xlstm import init_slstm, slstm
    from jax import set_mesh
    from repro.parallel.sharding import ShardingRules, use_rules

    cfg = get_config("xlstm-1.3b", smoke=True)
    params, _ = init_slstm(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 24, cfg.d_model),
                          jnp.float32)
    ref = slstm(params, x, cfg)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rules = ShardingRules(mesh, {"batch": "data", "seq": None,
                                 "embed": None, "inner": None,
                                 "w_embed": None})
    with set_mesh(mesh), use_rules(rules):
        out = jax.jit(lambda p, x: slstm(p, x, cfg))(params, x)
    err = float(jnp.max(jnp.abs(ref - out)))
    assert err < 1e-3, err
    print("OK", err)
    """)


@pytest.mark.slow
def test_pipeline_parallel_matches_plain_train_step():
    run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.config import OptimizerConfig, ParallelConfig, ShapeConfig
    from repro.models import init_lm
    from repro.parallel.pipeline import make_pp_train_step, pp_rules
    from jax import set_mesh
    from repro.parallel.sharding import ShardingRules, use_rules
    from repro.training.train_step import make_train_step, _loss_fn
    from repro.training.optimizer import init_opt_state
    from repro.data import SyntheticSource

    cfg = get_config("mistral-nemo-12b", smoke=True)
    shape = ShapeConfig("pp", 32, 8, "train")
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    pc = ParallelConfig(microbatches=4, remat="none",
                        attn_strategy="replicated")
    rules = pp_rules(ShardingRules(mesh, {"batch": ("data",),
                                          "layers": None}))
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticSource(cfg, shape, seed=0).batch(0).items()}
    with set_mesh(mesh), use_rules(rules):
        state = {"params": params, "opt": init_opt_state(params)}
        pp_step = jax.jit(make_pp_train_step(
            cfg, shape, OptimizerConfig(warmup_steps=0), pc, rules,
            q_chunk=32))
        st_pp, m_pp = pp_step(state, batch)
    ref_loss, _ = _loss_fn(params, batch, cfg,
                           ParallelConfig(remat="none"), q_chunk=32,
                           ssm_chunk=16)
    assert abs(float(m_pp["loss"]) - float(ref_loss)) < 2e-2
    plain = jax.jit(make_train_step(
        cfg, shape, OptimizerConfig(warmup_steps=0),
        ParallelConfig(microbatches=4, remat="none"), q_chunk=32))
    st_ref, _ = plain({"params": params, "opt": init_opt_state(params)},
                      batch)
    cos = []
    for a, b, p0 in zip(jax.tree.leaves(st_pp["params"]),
                        jax.tree.leaves(st_ref["params"]),
                        jax.tree.leaves(params)):
        da = (a - p0).astype(jnp.float32).ravel()
        db = (b - p0).astype(jnp.float32).ravel()
        n = float(jnp.linalg.norm(da) * jnp.linalg.norm(db))
        if n > 1e-12:
            cos.append(float(jnp.dot(da, db)) / n)
    assert min(cos) > 0.95, min(cos)
    print("OK", min(cos))
    """)
