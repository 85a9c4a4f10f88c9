"""Skew-adaptive exchange benchmark: the eighth decision node's win.

Two phases, one ``BENCH_skew.json`` (repo root):

1. **Zipf sweep.** The query at key-skew s in {0, 1.1, 1.5}, two arms per
   point on identical tables and runtime config: *unmitigated* (the skew
   node forced ``none`` — the pipelined plan as it was before the node
   existed) vs *auto* (the node binds on the observed shuffle histogram
   and picks none / salted / broadcast itself). The store emulates a
   disaggregated fabric (every byte a function reads or writes crosses
   the NIC at ``NET_BW``), so a heavy bucket's serialized read is what
   skew actually costs. Full runs assert: at s=1.5 the mitigated plan
   sustains >= 2x the unmitigated end-to-end rows/s, and at s=0 the node
   binds ``none`` within 5% of the baseline wall (same physical plan —
   the node's overhead is one histogram fold).
2. **Decision parity.** The same skewed workload planned through one
   workflow on both planes: the eight-node sequences — including the
   skew node's func/salt/heavy/hot extras — must be identical, because
   the simulator recomputes the exact histogram the runtime observes.

    PYTHONPATH=src python benchmarks/bench_skew.py [--smoke] [--reps N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ZIPFS = (0.0, 1.1, 1.5)
FACT_ROWS, DIM_ROWS, FACT_NODES = 1 << 19, 1 << 10, 32
SMOKE_FACT_ROWS, SMOKE_DIM_ROWS, SMOKE_FACT_NODES = 1 << 13, 1 << 9, 4
FANOUT = 8                     # pinned join fan-out (tables are synthetic)
NET_BW = 1e6                   # bytes/s per flow on the emulated fabric
SMOKE_NET_BW = 20e6
MAX_WORKERS = 32
OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_skew.json"
SMOKE_OUT_PATH = OUT_PATH.with_name("BENCH_skew_smoke.json")


def _pin_xla_single_thread() -> None:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false"
                               " intra_op_parallelism_threads=1").strip()


def _strategy():
    """``static_merge`` with the fan-out pinned to ``FANOUT``: the synthetic
    tables are small enough that the join decision's own scale rule would
    pick 1, which leaves a single bucket and nothing for skew to split."""
    from repro.analytics import QueryStrategy
    from repro.core.decisions import Decision

    class FanoutStrategy(QueryStrategy):
        def join_method(self, ctx):
            d = super().join_method(ctx)
            return Decision(d.func, FANOUT, d.schedule, extras=d.extras)

    return FanoutStrategy("static_merge")


def _run_arm(tables, fact_nodes: int, net_bw: float, force: str | None,
             reps: int):
    """One sweep arm: min-of-reps wall (plus one untimed warm-up rep so
    kernel compiles never land in a timed run) on a fresh runtime per rep.
    Returns ``(wall_s, skew_decision)``."""
    import numpy as np

    from repro.analytics import execute_query_runtime
    from repro.analytics.planner import build_query_workflow
    from repro.core.controllers import GlobalController

    from repro.runtime import Runtime

    fd, dd, ref = tables
    walls, last = [], None
    for rep in range(reps + 1):
        gc = GlobalController({n: 8 for n in range(fact_nodes)})
        rt = Runtime(gc, invoker="threads", net_bw=net_bw,
                     disaggregated=True, max_workers=MAX_WORKERS)
        wf = build_query_workflow(_strategy(), skew_force=force)
        try:
            t0 = time.perf_counter()
            got, _ = execute_query_runtime(fd, dd, _strategy(), runtime=rt,
                                           workflow=wf, pipeline=True)
            wall = time.perf_counter() - t0
            np.testing.assert_allclose(got, ref, atol=1e-3)
        finally:
            rt.store.close()
        if rep:                 # rep 0 is the compile warm-up
            walls.append(wall)
        last = wf.last_run.decisions["skew"]
    return min(walls), last


def _run_sweep(fact_rows: int, dim_rows: int, fact_nodes: int,
               net_bw: float, reps: int):
    from repro.analytics import synth_query_tables

    sweep = {}
    for s in ZIPFS:
        tables = synth_query_tables(fact_rows, dim_rows, seed=3, zipf=s,
                                    fact_nodes=fact_nodes)
        base_s, _ = _run_arm(tables, fact_nodes, net_bw, "none", reps)
        auto_s, skew_d = _run_arm(tables, fact_nodes, net_bw, None, reps)
        sweep[s] = {
            "unmitigated_s": base_s, "auto_s": auto_s,
            "unmitigated_rows_per_s": fact_rows / base_s,
            "auto_rows_per_s": fact_rows / auto_s,
            "speedup": base_s / auto_s,
            "decision": {"func": skew_d.func,
                         "salt": int(skew_d.extra("salt", 0)),
                         "hot_keys": [int(k) for k in
                                      skew_d.extra("hot_keys", ())],
                         "heavy_buckets": len(skew_d.extra("heavy", ())),
                         "ratio": round(float(skew_d.extra("ratio", 0.0)),
                                        3)},
        }
        print(f"# zipf={s}: unmitigated {base_s:.3f}s, auto[{skew_d.func}]"
              f" {auto_s:.3f}s ({base_s / auto_s:.2f}x)", file=sys.stderr)
    return sweep


def _run_parity(fact_rows: int, dim_rows: int):
    """Phase 2: eight-node decision parity, skew extras included, on the
    skewed workload (net emulation off — parity is about the control
    plane, not the clock)."""
    import numpy as np

    from repro.analytics import execute_query_runtime, synth_query_tables
    from repro.analytics.planner import (build_query_workflow,
                                         plan_query_with_workflow)
    from repro.analytics.simulator import ClusterSim
    from repro.core.controllers import GlobalController, PrivateController
    from repro.runtime import Runtime

    def view(run):
        return [(s, d.func, int(d.scale),
                 tuple(d.extra("heavy", ())), int(d.extra("salt", 0)),
                 tuple(d.extra("hot_keys", ())))
                for s, d in run.sequence]

    fd, dd, ref = synth_query_tables(fact_rows, dim_rows, seed=3, zipf=1.5,
                                     fact_nodes=4)
    wf = build_query_workflow(_strategy())
    rt = Runtime(GlobalController({n: 8 for n in range(4)}),
                 invoker="threads")
    try:
        got, _ = execute_query_runtime(fd, dd, _strategy(), runtime=rt,
                                       workflow=wf, pipeline=True)
        np.testing.assert_allclose(got, ref, atol=1e-3)
        seq_rt = view(wf.last_run)
    finally:
        rt.store.close()

    gc_sim = GlobalController({n: 8 for n in range(4)})
    sim = ClusterSim(gc_sim)
    pc = PrivateController("query", gc_sim, priority=10)
    plan_query_with_workflow(sim, pc, fd, dd, _strategy(), workflow=wf)
    sim.run()
    return seq_rt, view(wf.last_run)


def main(rows: list | None = None, smoke: bool = False, reps: int = 2,
         out_path: Path | str | None = None) -> dict:
    from repro.obs import write_bench_artifacts

    rows = [] if rows is None else rows
    if out_path is None:
        # smoke runs must not clobber the committed full-run artifact
        out_path = SMOKE_OUT_PATH if smoke else OUT_PATH
    fact_rows = SMOKE_FACT_ROWS if smoke else FACT_ROWS
    dim_rows = SMOKE_DIM_ROWS if smoke else DIM_ROWS
    fact_nodes = SMOKE_FACT_NODES if smoke else FACT_NODES
    net_bw = SMOKE_NET_BW if smoke else NET_BW

    # -- phase 1: zipf sweep, unmitigated vs auto --------------------------
    sweep = _run_sweep(fact_rows, dim_rows, fact_nodes, net_bw, reps)
    hot = sweep[1.5]
    assert hot["decision"]["func"] in ("salted", "broadcast"), hot
    assert sweep[0.0]["decision"]["func"] == "none", sweep[0.0]
    if not smoke:      # tiny smoke runs are dominated by fixed overheads
        # the tentpole claim: mitigation at least doubles end-to-end
        # throughput on the heavy-tailed workload ...
        assert hot["speedup"] >= 2.0, hot
        # ... and costs nothing when there is no skew to mitigate (the
        # uniform point binds "none": both arms run the identical plan)
        assert sweep[0.0]["speedup"] >= 0.95, sweep[0.0]
    rows.append(("skew/unmitigated_zipf1.5", sweep[1.5]["unmitigated_s"]
                 * 1e6, round(sweep[1.5]["unmitigated_rows_per_s"], 1)))
    rows.append(("skew/auto_zipf1.5", sweep[1.5]["auto_s"] * 1e6,
                 round(hot["speedup"], 3)))
    rows.append(("skew/auto_uniform", sweep[0.0]["auto_s"] * 1e6,
                 round(sweep[0.0]["speedup"], 3)))

    # -- phase 2: skew decision parity across planes -----------------------
    seq_rt, seq_sim = _run_parity(fact_rows, dim_rows)
    parity = seq_rt == seq_sim
    assert parity, (seq_rt, seq_sim)
    assert [s for s, *_ in seq_rt] == ["scan", "join", "exchange", "skew",
                                       "aggregate", "pipeline", "elastic",
                                       "tiering"]
    rows.append(("skew/decision_parity", 0.0, int(parity)))

    report = {
        "benchmark": "skew_adaptive_exchange",
        "config": {"fact_rows": fact_rows, "dim_rows": dim_rows,
                   "fact_nodes": fact_nodes, "fanout": FANOUT,
                   "net_bw": net_bw, "reps": reps, "smoke": smoke},
        "sweep": {str(s): v for s, v in sweep.items()},
        "decision_parity": {
            "identical": parity,
            "sequence": [{"node": s, "func": f, "scale": sc,
                          "heavy_buckets": len(h), "salt": salt,
                          "hot_keys": list(hk)}
                         for s, f, sc, h, salt, hk in seq_rt]},
        "observability": write_bench_artifacts(out_path, apps=["query"]),
    }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path} (zipf1.5 {hot['speedup']:.2f}x via "
          f"{hot['decision']['func']}, uniform "
          f"{sweep[0.0]['speedup']:.2f}x, parity={parity})",
          file=sys.stderr)
    return report


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small tables, 1 rep (CI)")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    _pin_xla_single_thread()
    main(smoke=args.smoke,
         reps=args.reps if args.reps is not None
         else (1 if args.smoke else 2),
         out_path=args.out)
