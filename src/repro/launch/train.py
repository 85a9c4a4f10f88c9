"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt /tmp/repro_run

Runs the full stack: config -> decision workflow (strategy/scale/schedule)
-> sharded train_step -> data pipeline -> supervisor (checkpoint/restart,
straggler watchdog). On CPU use --smoke (reduced config); on a real TPU
slice the same driver runs the full config against the production mesh.
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.config import OptimizerConfig, ParallelConfig, ShapeConfig
from repro.core.decisions import DecisionContext
from repro.ckpt import Supervisor, latest_step, load_checkpoint
from repro.data import Prefetcher, SyntheticSource
from repro.launch.mesh import make_smoke_mesh
from repro.models import init_lm
from repro.parallel.sharding import use_rules
from repro.parallel.strategies import make_rules, strategy_node
from repro.training import init_opt_state, make_train_step
from jax import set_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeConfig("train_cli", args.seq, args.batch, "train")
    mesh = make_smoke_mesh()

    # control plane: resolve the decision tuple for this cell
    node = strategy_node(cfg, shape, mesh)
    decision = node.decide(DecisionContext())
    pc = decision.extra("parallel_config")
    if args.microbatches > 1:
        import dataclasses
        pc = dataclasses.replace(pc, microbatches=args.microbatches)
    rules = make_rules(mesh, cfg, shape, pc)
    print(f"[train] {cfg.name} decision: {decision.func} "
          f"scale={pc.microbatches} schedule={decision.schedule.policy}")

    opt_cfg = OptimizerConfig(warmup_steps=10)
    with set_mesh(mesh), use_rules(rules):
        params, _ = init_lm(cfg, jax.random.PRNGKey(0))
        state = {"params": params, "opt": init_opt_state(params)}
        start = 0
        if args.resume and latest_step(args.ckpt) is not None:
            state, extra = load_checkpoint(args.ckpt, like=state)
            start = extra.get("step", 0)
            print(f"[train] resumed from step {start}")

        step_fn = jax.jit(make_train_step(cfg, shape, opt_cfg, pc,
                                          total_steps=args.steps,
                                          q_chunk=min(args.seq, 512),
                                          ssm_chunk=min(args.seq, 64)))
        source = SyntheticSource(cfg, shape, seed=1)
        prefetch = Prefetcher(source, start_step=start)
        losses = []

        def wrapped_step(st, batch):
            st, metrics = step_fn(st, batch)
            return st, metrics

        def batch_fn(step):
            s, b = prefetch.next()
            return {k: jnp.asarray(v) for k, v in b.items()}

        sup = Supervisor(wrapped_step, batch_fn, args.ckpt,
                         ckpt_every=args.ckpt_every)

        # run with logging via a small shim
        t0 = time.time()
        step = start
        orig_step_fn = sup.step_fn

        def logging_step(st, batch):
            nonlocal step
            st, metrics = orig_step_fn(st, batch)
            step += 1
            if step % args.log_every == 0:
                loss = float(metrics["loss"])
                losses.append(loss)
                tput = shape.tokens_per_step * args.log_every \
                    / max(time.time() - logging_step.t, 1e-9)
                logging_step.t = time.time()
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"grad_norm {float(metrics['grad_norm']):7.3f} "
                      f"tok/s {tput_fmt(tput=tput)}")
            return st, metrics

        def tput_fmt(tput):
            return f"{tput:,.0f}"

        logging_step.t = time.time()
        sup.step_fn = logging_step
        state, final = sup.run(state, args.steps, start_step=start)
        prefetch.close()
        wall = time.time() - t0
        print(f"[train] finished at step {final} in {wall:.1f}s; "
              f"restarts={sup.restarts} stragglers={len(sup.stragglers)}")
        if len(losses) >= 2:
            print(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
                  f"({'improved' if losses[-1] < losses[0] else 'flat'})")
    return losses


if __name__ == "__main__":
    main()
