"""Sharding rules, strategy decision nodes, and the HLO cost analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import SHAPES, ParallelConfig, ShapeConfig
from repro.launch.hlo_analysis import analyze, split_computations
from repro.launch.mesh import make_smoke_mesh
from repro.parallel.sharding import ShardingRules, pad_to_multiple
from repro.parallel.strategies import (
    pick_attention_strategy,
    pick_moe_strategy,
    plan_cell,
)


class FakeMesh:
    """Shape-only stand-in so strategy tests don't build 512 devices."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.devices = np.empty(tuple(shape.values()), dtype=object)


SINGLE = FakeMesh({"data": 16, "model": 16})


# -- ShardingRules -----------------------------------------------------------


def test_spec_deduplicates_mesh_axes():
    rules = ShardingRules(None, {"seq": "model", "mlp": "model",
                                 "batch": "data"})
    spec = rules.spec("batch", "seq", "mlp")
    # second use of "model" must drop out (an axis can shard only one dim)
    assert spec == jax.sharding.PartitionSpec("data", "model", None)


def test_spec_handles_tuple_axes():
    rules = ShardingRules(None, {"batch": ("pod", "data")})
    assert rules.spec("batch", None) == jax.sharding.PartitionSpec(
        ("pod", "data"), None)


def test_pad_to_multiple():
    assert pad_to_multiple(151655, 128) == 151680
    assert pad_to_multiple(128, 128) == 128


# -- strategy decisions (the paper's decision tuple for LM cells) --------------


def test_attention_strategy_gqa_prefers_kv_broadcast():
    """GQA: broadcasting the small KV (hash-join move, 2*res + kv wire)
    beats classic Megatron head-TP (4*res wire) — the decision node picks
    seq_tp even though 32 heads divide the axis."""
    cfg = get_config("mistral-nemo-12b")      # 32H but kv=8 (tiny KV)
    assert pick_attention_strategy(cfg, SHAPES["train_4k"], 16) == "seq_tp"


def test_attention_strategy_mha_divisible_picks_head_tp():
    """MHA (kv == heads): the KV 'small table' isn't small, broadcast loses
    its edge; with divisible heads, head-TP wins the tie."""
    cfg = get_config("moonshot-v1-16b-a3b")   # 16H, kv=16, divisible
    assert pick_attention_strategy(cfg, SHAPES["train_4k"], 16) == "head_tp"


def test_attention_strategy_indivisible_heads_seq_tp():
    cfg = get_config("qwen1.5-4b")            # 20 heads: head_tp infeasible
    assert pick_attention_strategy(cfg, SHAPES["train_4k"], 16) == "seq_tp"


def test_attention_strategy_decode_uses_kv_shard():
    cfg = get_config("qwen2-72b")
    assert pick_attention_strategy(cfg, SHAPES["decode_32k"], 16) \
        == "decode_kv_shard"


def test_attention_strategy_attention_free():
    cfg = get_config("xlstm-1.3b")
    assert pick_attention_strategy(cfg, SHAPES["train_4k"], 16) == "none"


def test_moe_strategy_prefers_explicit_shuffle_for_training_tokens():
    cfg = get_config("moonshot-v1-16b-a3b")
    assert pick_moe_strategy(cfg, SHAPES["train_4k"], 16) == "shard_map_a2a"


def test_moe_strategy_prefers_gather_for_decode():
    cfg = get_config("granite-moe-1b-a400m")
    assert pick_moe_strategy(cfg, SHAPES["decode_32k"], 16) == "gather"


def test_plan_cell_resolves_everything():
    cfg = get_config("qwen2-72b")
    pc = plan_cell(cfg, SHAPES["train_4k"], SINGLE)
    assert pc.attn_strategy == "seq_tp"       # GQA kv=8: KV broadcast wins
    assert pc.fsdp in ("on", "off") and pc.fsdp == "on"   # 72B needs ZeRO
    assert pc.microbatches >= 1
    assert pc.sequence_sharded_residual is True


def test_plan_cell_small_model_no_fsdp():
    cfg = get_config("granite-moe-1b-a400m")
    pc = plan_cell(cfg, SHAPES["train_4k"], SINGLE)
    assert pc.fsdp == "off"


def test_plan_cell_respects_overrides():
    cfg = get_config("llama3.2-3b")
    pc = plan_cell(cfg, SHAPES["train_4k"], SINGLE,
                   ParallelConfig(attn_strategy="replicated",
                                  microbatches=4))
    assert pc.attn_strategy == "replicated"
    assert pc.microbatches == 4


# -- HLO analyzer --------------------------------------------------------------


def test_hlo_analyzer_multiplies_trip_counts():
    def body(c, x):
        return c @ x, ()

    def f(c, xs):
        return jax.lax.scan(body, c, xs)[0]

    c = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    for n in (4, 12):
        xs = jax.ShapeDtypeStruct((n, 64, 64), jnp.float32)
        compiled = jax.jit(f).lower(c, xs).compile()
        costs = analyze(compiled.as_text())
        assert costs.flops == pytest.approx(n * 2 * 64 ** 3, rel=1e-6)


def test_hlo_analyzer_matches_xla_on_straightline():
    """On a loop-free program the parser must agree with XLA's own count."""
    def f(a, b, c):
        return (a @ b) @ c

    spec = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(spec, spec, spec).compile()
    xla_flops = compiled.cost_analysis()["flops"]
    parsed = analyze(compiled.as_text()).flops
    assert parsed == pytest.approx(xla_flops, rel=1e-6)


def test_hlo_analyzer_nested_scans():
    def inner(c, x):
        return c @ x, ()

    def outer(c, xs):
        def step(c, _):
            c2, _ = jax.lax.scan(inner, c, xs)
            return c2, ()
        return jax.lax.scan(step, c, None, length=3)[0]

    c = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    xs = jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)
    compiled = jax.jit(outer).lower(c, xs).compile()
    costs = analyze(compiled.as_text())
    assert costs.flops == pytest.approx(3 * 5 * 2 * 32 ** 3, rel=1e-6)


def test_split_computations_finds_entry():
    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32)).compile()
    comps, entry = split_computations(compiled.as_text())
    assert entry in comps and comps
