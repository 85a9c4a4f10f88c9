"""Executor benchmark: barrier vs dependency-driven DAG execution.

Runs the TPC-DS-like sub-query end-to-end on the serverless runtime under
the ``threads`` invoker (``--invoker process`` runs the same sweep on the
process-backed worker plane) for all four strategies, once with the legacy
barrier-per-stage executor and once with the dependency-driven scheduler,
and emits ``BENCH_executor.json`` (repo root) with per-strategy wall-clock
and speedups.

The store runs in disaggregated mode (the Lambada/Pocket model: every byte
read from or written to the ephemeral store crosses the network at
``NET_BW``), which is where dependency-driven scheduling pays: one side's
storage transfers overlap the other side's compute instead of serializing
behind a per-stage barrier. XLA intra-op threading is pinned to one thread
(standalone runs) so the measurement isolates *inter-stage* scheduling.

    PYTHONPATH=src python benchmarks/bench_executor.py [--smoke] [--reps N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

STRATEGIES = ("static_merge", "static_hash", "dynamic", "dynamic_fig6")
NET_BW = 100e6            # bytes/s per function <-> storage link
ROWS, DIM_ROWS = 1 << 19, 1 << 18
SMOKE_ROWS, SMOKE_DIM_ROWS = 1 << 12, 1 << 11
OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_executor.json"
SMOKE_OUT_PATH = OUT_PATH.with_name("BENCH_executor_smoke.json")


def _pin_xla_single_thread() -> None:
    """Must run before jax initializes; isolates inter-stage scheduling."""
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false"
                               " intra_op_parallelism_threads=1").strip()


def _make_tables(rows: int, dim_rows: int):
    from repro.analytics import synth_query_tables

    # fact on nodes {0,1}, dim on {2,3}: scans and exchanges of the two
    # sides are fully independent stages on a 4-node cluster
    return synth_query_tables(rows, dim_rows, seed=1, fact_nodes=range(2),
                              dim_nodes=[2, 3])


def _run_once(fd, dd, strategy: str, barrier: bool,
              invoker: str = "threads", max_workers: int = 8,
              store_backend: str = "memory"):
    from repro.analytics import QueryStrategy, execute_query_runtime
    from repro.core.controllers import GlobalController
    from repro.runtime import Runtime

    from repro.obs import get_tracer

    # one run per trace buffer: the exported artifact is the last run
    get_tracer().clear()
    gc = GlobalController({n: 8 for n in range(4)})
    runtime = Runtime(gc, invoker=invoker, net_bw=NET_BW,
                      disaggregated=True, max_workers=max_workers,
                      storage=store_backend)
    try:
        t0 = time.perf_counter()
        got, _ = execute_query_runtime(fd, dd, QueryStrategy(strategy),
                                       runtime=runtime, barrier=barrier)
        wall = time.perf_counter() - t0
        return wall, got
    finally:
        if invoker == "process":
            runtime.invoker.shutdown()
        runtime.store.close()       # disk primary: remove the spill tempdir


def main(rows: list | None = None, smoke: bool = False, reps: int = 3,
         out_path: Path | str | None = None,
         invoker: str = "threads", max_workers: int = 8,
         store_backend: str = "memory") -> dict:
    import numpy as np

    from repro.obs import write_bench_artifacts

    own = rows is None
    rows = [] if own else rows
    if out_path is None:
        # smoke runs must not clobber the committed full-run artifact
        out_path = SMOKE_OUT_PATH if smoke else OUT_PATH
    n_rows, n_dim = (SMOKE_ROWS, SMOKE_DIM_ROWS) if smoke \
        else (ROWS, DIM_ROWS)
    fd, dd, ref = _make_tables(n_rows, n_dim)

    results: dict = {}
    for strat in STRATEGIES:
        entry = {}
        for mode, barrier in (("barrier", True), ("deps", False)):
            walls = []
            for _ in range(reps):
                wall, got = _run_once(fd, dd, strat, barrier,
                                      invoker=invoker,
                                      max_workers=max_workers,
                                      store_backend=store_backend)
                np.testing.assert_allclose(got, ref, atol=1e-2)
                walls.append(wall)
            entry[f"{mode}_s"] = min(walls)
        entry["speedup"] = entry["barrier_s"] / entry["deps_s"]
        results[strat] = entry
        rows.append((f"executor/{strat}/deps", entry["deps_s"] * 1e6,
                     round(entry["speedup"], 3)))

    barrier_total = sum(r["barrier_s"] for r in results.values())
    deps_total = sum(r["deps_s"] for r in results.values())
    report = {
        "benchmark": "executor_barrier_vs_deps",
        "invoker": invoker,
        "config": {"rows": n_rows, "dim_rows": n_dim, "nodes": 4,
                   "slots_per_node": 8, "net_bw": NET_BW,
                   "disaggregated": True, "reps": reps, "smoke": smoke,
                   "store_backend": store_backend},
        "results": results,
        "summary": {"barrier_total_s": barrier_total,
                    "deps_total_s": deps_total,
                    "speedup": barrier_total / deps_total},
        # trace of the last timed (deps) run + the query's critical path
        "observability": write_bench_artifacts(out_path, apps=["query"]),
    }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    rows.append(("executor/total/deps", deps_total * 1e6,
                 round(barrier_total / deps_total, 3)))
    if own:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
    print(f"# wrote {out_path}: barrier {barrier_total * 1e3:.1f}ms, "
          f"deps {deps_total * 1e3:.1f}ms "
          f"({barrier_total / deps_total:.2f}x)", file=sys.stderr)
    return report


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny tables, 1 rep (CI: exercises the "
                         "dependency-driven path, no perf claim)")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_executor.json, or "
                         "BENCH_executor_smoke.json under --smoke)")
    ap.add_argument("--invoker", default="threads",
                    choices=["threads", "process", "inline"],
                    help="function backend (process: real worker "
                         "subprocesses; cap --max-workers on small hosts)")
    ap.add_argument("--max-workers", type=int, default=8)
    ap.add_argument("--store-backend", default="memory",
                    choices=["memory", "disk"],
                    help="shuffle store primary tier (disk: every blob "
                         "round-trips through real files in a tempdir)")
    args = ap.parse_args()
    _pin_xla_single_thread()
    main(smoke=args.smoke,
         reps=args.reps if args.reps is not None else (1 if args.smoke else 3),
         out_path=args.out, invoker=args.invoker,
         max_workers=args.max_workers, store_backend=args.store_backend)
