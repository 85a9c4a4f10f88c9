import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. runs the control-plane decision workflow (strategy/scale/schedule),
  2. builds the step function (train_step / prefill forward / decode step),
  3. ``jax.jit(...).lower(...).compile()`` against ShapeDtypeStruct inputs
     (no allocation) on the production mesh,
  4. records memory_analysis / cost_analysis / per-collective byte counts
     into a JSON artifact consumed by benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-4b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import time
import traceback
from functools import partial
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.common import applicable_shapes, input_specs
from repro.core.config import SHAPES, ModelConfig, OptimizerConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.models.lm import (
    decode_state_axes,
    decode_step,
    forward,
    init_decode_state,
    init_lm,
)
from repro.parallel.sharding import ShardingRules, use_rules
from repro.parallel.strategies import make_rules, plan_cell, strategy_node
from repro.core.decisions import DecisionContext
from repro.training.optimizer import init_opt_state, opt_state_axes
from repro.training.train_step import make_train_step
from repro.launch.hlo_analysis import analyze
from jax import set_mesh

DEFAULT_OUT = Path("experiments/dryrun")


def _shape_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def _eval_shape_with_axes(fn):
    captured = {}

    def wrapper():
        out, axes = fn()
        captured["axes"] = axes
        return out

    shapes = jax.eval_shape(wrapper)
    return shapes, captured["axes"]


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               pc_overrides=None, profile: str = "optimized"):
    """Returns (fn, example_args(ShapeDtypeStructs), in_shardings, rules, pc).

    This is where the paper's decision workflow executes: strategy_node emits
    the decision tuple and make_rules materializes it as sharding rules.
    """
    if pc_overrides:
        # overrides participate in planning (mb/fsdp depend on them)
        from repro.core.config import ParallelConfig
        pc = plan_cell(cfg, shape, mesh, ParallelConfig(**pc_overrides),
                       profile=profile)
    else:
        pc = plan_cell(cfg, shape, mesh, profile=profile)
    rules = make_rules(mesh, cfg, shape, pc)

    params_shapes, axes = _eval_shape_with_axes(
        lambda: init_lm(cfg, jax.random.PRNGKey(0)))
    p_shardings = jax.tree.map(
        lambda a: rules.sharding(*a), axes,
        is_leaf=lambda v: isinstance(v, tuple)
        and all(isinstance(x, (str, type(None))) for x in v))

    inp = input_specs(cfg, shape)
    inp_axes = {
        "tokens": ("batch", "seq"),
        "labels": ("batch", "seq"),
        "patch_embeds": ("batch", None, "embed"),
        "frame_embeds": ("batch", "seq", None),
    }
    inp_shardings = {k: rules.sharding(*inp_axes[k]) for k in inp}

    if shape.mode == "train":
        if pc.pod_axis_role == "pipeline":
            # packing decision: pipeline the layer stack over pods
            from repro.parallel.pipeline import (
                make_pp_train_step,
                pp_applicable,
                pp_rules,
            )
            assert pp_applicable(cfg, shape, mesh, pc), \
                "pipeline schedule inapplicable to this cell"
            rules = pp_rules(rules)
            p_shardings = jax.tree.map(
                lambda a: rules.sharding(*a), axes,
                is_leaf=lambda v: isinstance(v, tuple)
                and all(isinstance(x, (str, type(None))) for x in v))
            inp_shardings = {k: rules.sharding(*inp_axes[k]) for k in inp}
        opt_shapes = jax.eval_shape(init_opt_state, params_shapes)
        opt_ax = opt_state_axes(axes)
        o_shardings = jax.tree.map(
            lambda a: rules.sharding(*a), opt_ax,
            is_leaf=lambda v: isinstance(v, tuple)
            and all(isinstance(x, (str, type(None))) for x in v))
        state_shapes = {"params": params_shapes, "opt": opt_shapes}
        state_shardings = {"params": p_shardings, "opt": o_shardings}
        if pc.pod_axis_role == "pipeline":
            from repro.parallel.pipeline import make_pp_train_step
            fn = make_pp_train_step(cfg, shape, OptimizerConfig(), pc, rules)
        else:
            fn = make_train_step(cfg, shape, OptimizerConfig(), pc)
        return (fn, (state_shapes, inp), (state_shardings, inp_shardings),
                (state_shardings, None), rules, pc)

    if shape.mode == "prefill":
        fn = partial(forward, cfg=cfg, remat=pc.remat)
        return (fn, (params_shapes, inp), (p_shardings, inp_shardings),
                (None,), rules, pc)

    # decode
    state_shapes, d_axes = _eval_shape_with_axes(
        lambda: (init_decode_state(cfg, shape.global_batch, shape.seq_len),
                 decode_state_axes(cfg)))
    d_shardings = jax.tree.map(
        lambda a: rules.sharding(*a), d_axes,
        is_leaf=lambda v: isinstance(v, tuple)
        and all(isinstance(x, (str, type(None))) for x in v))
    fn = partial(decode_step, cfg=cfg)
    return (fn, (params_shapes, state_shapes, inp["tokens"]),
            (p_shardings, d_shardings, inp_shardings["tokens"]),
            (None, d_shardings), rules, pc)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path = DEFAULT_OUT, pc_overrides=None,
             tag: str = "", profile: str = "optimized") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "status": "ok"}
    if shape_name not in applicable_shapes(cfg):
        record["status"] = "skipped"
        record["reason"] = ("long_500k requires sub-quadratic attention "
                            "(see DESIGN.md §Arch-applicability)")
        out_dir.mkdir(parents=True, exist_ok=True)
        suffix = f"-{tag}" if tag else ""
        (out_dir / f"{arch}--{shape_name}--{mesh_name}{suffix}.json"
         ).write_text(json.dumps(record, indent=2))
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: SKIPPED")
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        with set_mesh(mesh):
            fn, args, in_sh, out_sh_hint, rules, pc = build_cell(
                cfg, shape, mesh, pc_overrides, profile=profile)
            # donate the mutable state (train: params+opt; decode: caches) —
            # production steps alias these buffers, and without donation the
            # copied outputs double the temp/peak accounting
            donate = (0,) if shape.mode == "train" else \
                (1,) if shape.mode == "decode" else ()
            with use_rules(rules):
                jitted = jax.jit(fn, in_shardings=in_sh,
                                 donate_argnums=donate)
                lowered = jitted.lower(*args)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            parsed = analyze(hlo)

        from repro.parallel.strategies import exact_param_bytes_per_chip
        n_dev = mesh_devices(mesh)
        record["param_bytes_per_device"] = exact_param_bytes_per_chip(
            cfg, rules)
        record.update({
            "parallel_config": {
                "attn_strategy": pc.attn_strategy,
                "moe_strategy": pc.moe_strategy,
                "layout": pc.layout,
                "microbatches": pc.microbatches,
                "remat": pc.remat,
                "fsdp": pc.fsdp,
                "mlp_mode": pc.mlp_mode,
                "causal_skip": pc.causal_skip,
                "kv_compress": pc.kv_compress,
                "pod_axis_role": pc.pod_axis_role,
            },
            "devices": n_dev,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "tokens_per_step": shape.tokens_per_step,
            # per-device numbers (the HLO is the per-device SPMD program)
            "flops_per_device": parsed.flops,
            "xla_cost_flops_once": float(cost.get("flops", -1.0))
            if cost else -1.0,
            "xla_bytes_accessed_once": float(cost.get("bytes accessed", -1.0))
            if cost else -1.0,
            "collective_bytes_by_kind": parsed.collective_bytes,
            "collective_counts": parsed.collective_counts,
            "collective_bytes": parsed.total_collective_bytes,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
        })
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "alias_size_in_bytes",
                         "peak_memory_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    record[attr] = int(v)
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
              f"flops/dev={record['flops_per_device']:.3e}, "
              f"coll={record['collective_bytes']:.3e}B)")
    except Exception as e:  # noqa: BLE001 - record and continue
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"FAILED {record['error']}")

    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"-{tag}" if tag else ""
    path = out_dir / f"{arch}--{shape_name}--{mesh_name}{suffix}.json"
    path.write_text(json.dumps(record, indent=2, default=str))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--tag", default="")
    ap.add_argument("--profile", default="optimized",
                    choices=["optimized", "baseline"])
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape_name, multi, Path(args.out),
                               tag=args.tag, profile=args.profile)
                failures += rec["status"] == "error"
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")
    print("[dryrun] all requested cells passed")


if __name__ == "__main__":
    main()
