"""Tiered shuffle-storage benchmark: cold-data scans, spill-vs-recompute
under quota pressure, and cross-plane tiering decision parity.

Three phases, one ``BENCH_tiering.json`` (repo root):

1. **Cold data.** Inputs seeded straight into the emulated object store
   (latency + bandwidth + dollars), then the query runs twice on the same
   runtime: the first touch scans through the object tier (paying its cost
   model, promoting the inputs into memory), the warm re-query reuses the
   promoted inputs in place. Warm must beat first-touch on makespan, and
   the second run bills zero additional storage dollars.
2. **Spill vs evict-and-recompute.** The query with a fault plan that
   loses the partial-aggregate stage at its first read — forcing recovery
   to re-read reclaimed upstream state. The spill arm runs under a store
   quota with a disk backend: the tiering node demotes reclaimed stages,
   so recovery reads the spilled join output back (shallow). The baseline
   arm is the pre-tiering always-evict behavior (eager reclaim drops
   consumed stages outright): the same loss replays the whole producer
   chain — scan, shuffle, join — before the aggregate can retry. Spill
   must win on both re-executed invocations and (full runs) makespan.
3. **Decision parity.** The full query planned through one workflow on
   both planes with quota and cold tiers engaged: the seven-node decision
   sequences — including the tiering node's per-stage spill plan — must
   be identical.

    PYTHONPATH=src python benchmarks/bench_tiering.py [--smoke] [--reps N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

FACT_ROWS, DIM_ROWS = 1 << 14, 1 << 11
SMOKE_FACT_ROWS, SMOKE_DIM_ROWS = 1 << 12, 1 << 9
OBJ_LATENCY_S = 0.002          # per-request first-byte latency (emulated)
OBJ_BW = 200e6                 # bytes/s per stream (emulated)
OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_tiering.json"
SMOKE_OUT_PATH = OUT_PATH.with_name("BENCH_tiering_smoke.json")


def _pin_xla_single_thread() -> None:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false"
                               " intra_op_parallelism_threads=1").strip()


def _tables(fact_rows: int, dim_rows: int):
    from repro.analytics import synth_query_tables

    return synth_query_tables(fact_rows, dim_rows, seed=5)


def _run_cold_then_warm(tables, reps: int):
    """Phase 1: object-store-seeded inputs, first touch vs warm re-query."""
    import numpy as np

    from repro.analytics import QueryStrategy, execute_query_runtime
    from repro.core.controllers import GlobalController
    from repro.runtime import ObjectStoreBackend, Runtime

    fd, dd, ref = tables
    first_walls, warm_walls = [], []
    cost_first = promotions = 0
    for _ in range(reps):
        gc = GlobalController({n: 8 for n in range(4)})
        rt = Runtime(gc, spill_backends=[
            ObjectStoreBackend(latency_s=OBJ_LATENCY_S, bw=OBJ_BW)])
        try:
            t0 = time.perf_counter()
            got, _ = execute_query_runtime(fd, dd,
                                           QueryStrategy("static_merge"),
                                           runtime=rt, seed_tier="object")
            first_walls.append(time.perf_counter() - t0)
            np.testing.assert_allclose(got, ref, atol=1e-3)
            cost_first = rt.store.storage_cost["query"]
            promotions = len(rt.store.promotions)
            t0 = time.perf_counter()
            got, _ = execute_query_runtime(fd, dd,
                                           QueryStrategy("static_merge"),
                                           runtime=rt, reuse_inputs=True)
            warm_walls.append(time.perf_counter() - t0)
            np.testing.assert_allclose(got, ref, atol=1e-3)
            # the warm run must not touch the object tier again
            assert rt.store.storage_cost["query"] == cost_first
        finally:
            rt.store.close()
    return {"first_touch_s": min(first_walls), "warm_s": min(warm_walls),
            "warm_speedup": min(first_walls) / min(warm_walls),
            "storage_cost_dollars": cost_first,
            "input_promotions": promotions}


def _run_quota(tables, spill: bool, reps: int):
    """Phase 2, one arm. ``spill=True``: store quota + disk backend, the
    tiering node demotes reclaimed stages. ``spill=False``: the pre-tiering
    always-evict behavior — eager reclaim drops consumed stages, recovery
    recomputes them through lineage."""
    import numpy as np

    from repro.analytics import QueryStrategy, execute_query_runtime
    from repro.core.controllers import GlobalController
    from repro.runtime import (DiskBackend, FaultInjector, FaultPlan,
                               Runtime, StageLossFault)

    fd, dd, ref = tables
    quota = None
    if spill:
        # the tightest quota the barrier-less executor admits is the
        # query's own unconstrained peak; it is what engages the tiering
        # decision (no quota -> "keep" -> no spill policy)
        got, rt0 = execute_query_runtime(fd, dd,
                                         QueryStrategy("static_merge"))
        np.testing.assert_allclose(got, ref, atol=1e-3)
        quota = rt0.store.peak_bytes["query"]

    walls, reexec, recovered, demos = [], 0, (), 0
    for _ in range(reps):
        gc = GlobalController({n: 8 for n in range(4)})
        rt = Runtime(gc, spill_backends=[DiskBackend()] if spill else None)
        if quota is not None:
            rt.store.set_quota("query", quota)
        FaultInjector(FaultPlan(losses=[
            StageLossFault("partials", on_read=1)])).install(rt)
        try:
            t0 = time.perf_counter()
            got, _ = execute_query_runtime(fd, dd,
                                           QueryStrategy("static_merge"),
                                           runtime=rt)
            walls.append(time.perf_counter() - t0)
            np.testing.assert_allclose(got, ref, atol=1e-3)
            assert rt.recoveries
            reexec = sum(ev.invocations for ev in rt.recoveries)
            recovered = tuple(s for ev in rt.recoveries
                              for s in ev.recovered)
            demos = len(rt.store.demotions)
        finally:
            rt.store.close()
    return {"makespan_s": min(walls), "reexecuted_invocations": reexec,
            "recovered_stages": list(recovered), "demotions": demos,
            "quota_bytes": quota}


def _run_parity(tables):
    """Phase 3: seven-node decision parity with quota + cold tiers."""
    import numpy as np

    from repro.analytics import QueryStrategy, execute_query_runtime
    from repro.analytics.planner import (build_query_workflow,
                                         plan_query_with_workflow)
    from repro.analytics.simulator import ClusterSim
    from repro.core.controllers import GlobalController, PrivateController
    from repro.runtime import DiskBackend, ObjectStoreBackend, Runtime

    fd, dd, ref = tables
    got, rt0 = execute_query_runtime(fd, dd, QueryStrategy("dynamic"))
    quota = rt0.store.peak_bytes["query"]

    wf = build_query_workflow(QueryStrategy("dynamic"))
    gc_rt = GlobalController({n: 8 for n in range(4)})
    rt = Runtime(gc_rt, spill_backends=[
        DiskBackend(),
        ObjectStoreBackend(latency_s=0.0, bw=None)])
    rt.store.set_quota("query", quota)
    try:
        got, _ = execute_query_runtime(fd, dd, QueryStrategy("dynamic"),
                                       runtime=rt, workflow=wf)
        np.testing.assert_allclose(got, ref, atol=1e-3)
        spec = rt.store.storage_spec()
        seq_rt = [(s, d.func, d.scale, d.extra("plan", None))
                  for s, d in wf.last_run.sequence]
    finally:
        rt.store.close()

    gc_sim = GlobalController({n: 8 for n in range(4)})
    sim = ClusterSim(gc_sim, storage_spec=spec,
                     store_quotas={"query": quota})
    pc = PrivateController("query", gc_sim, priority=10)
    plan_query_with_workflow(sim, pc, fd, dd, QueryStrategy("dynamic"),
                             workflow=wf)
    sim.run()
    seq_sim = [(s, d.func, d.scale, d.extra("plan", None))
               for s, d in wf.last_run.sequence]
    return seq_rt, seq_sim


def main(rows: list | None = None, smoke: bool = False, reps: int = 3,
         out_path: Path | str | None = None) -> dict:
    from repro.obs import write_bench_artifacts

    rows = [] if rows is None else rows
    if out_path is None:
        # smoke runs must not clobber the committed full-run artifact
        out_path = SMOKE_OUT_PATH if smoke else OUT_PATH
    fact_rows = SMOKE_FACT_ROWS if smoke else FACT_ROWS
    dim_rows = SMOKE_DIM_ROWS if smoke else DIM_ROWS
    tables = _tables(fact_rows, dim_rows)

    # -- phase 1: cold-data first touch vs warm re-query -------------------
    cold = _run_cold_then_warm(tables, reps)
    assert cold["warm_speedup"] > 1.0, cold
    rows.append(("tiering/cold_first_touch", cold["first_touch_s"] * 1e6,
                 round(cold["warm_speedup"], 3)))
    rows.append(("tiering/warm_requery", cold["warm_s"] * 1e6,
                 cold["input_promotions"]))
    print(f"# cold data: first touch {cold['first_touch_s']:.3f}s "
          f"(${cold['storage_cost_dollars']:.2e}), warm re-query "
          f"{cold['warm_s']:.3f}s ({cold['warm_speedup']:.2f}x)",
          file=sys.stderr)

    # -- phase 2: spill vs evict-and-recompute under quota -----------------
    spill = _run_quota(tables, spill=True, reps=reps)
    evict = _run_quota(tables, spill=False, reps=reps)
    assert spill["demotions"], spill
    # the spilled join output is read back, not recomputed: recovery stays
    # shallow, the always-evict arm replays the whole producer chain
    assert spill["reexecuted_invocations"] < \
        evict["reexecuted_invocations"], (spill, evict)
    if not smoke:       # tiny smoke runs are dominated by fixed overheads
        assert spill["makespan_s"] < evict["makespan_s"], (spill, evict)
    speedup = evict["makespan_s"] / spill["makespan_s"]
    rows.append(("tiering/quota_spill", spill["makespan_s"] * 1e6,
                 round(speedup, 3)))
    rows.append(("tiering/always_evict", evict["makespan_s"] * 1e6,
                 evict["reexecuted_invocations"]))
    print(f"# recovery: spill {spill['makespan_s']:.3f}s "
          f"({spill['reexecuted_invocations']} re-exec, "
          f"{spill['demotions']} demotions) vs always-evict "
          f"{evict['makespan_s']:.3f}s "
          f"({evict['reexecuted_invocations']} re-exec) -> {speedup:.2f}x",
          file=sys.stderr)

    # -- phase 3: tiering decision parity across planes --------------------
    seq_rt, seq_sim = _run_parity(tables)
    parity = seq_rt == seq_sim
    assert parity, (seq_rt, seq_sim)
    assert [s for s, *_ in seq_rt] == ["scan", "join", "exchange",
                                      "skew", "aggregate", "pipeline",
                                      "elastic", "tiering"]

    report = {
        "benchmark": "tiered_shuffle_storage",
        "config": {"fact_rows": fact_rows, "dim_rows": dim_rows,
                   "reps": reps, "smoke": smoke,
                   "object_latency_s": OBJ_LATENCY_S, "object_bw": OBJ_BW},
        "cold_data": cold,
        "quota_pressure": {"spill": spill, "evict_and_recompute": evict,
                           "spill_makespan_speedup": round(speedup, 3)},
        "decision_parity": {
            "identical": parity,
            "sequence": [{"node": s, "func": f, "scale": int(sc),
                          "plan": list(map(list, p)) if p else p}
                         for s, f, sc, p in seq_rt]},
        "observability": write_bench_artifacts(out_path, apps=["query"]),
    }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path} (warm {cold['warm_speedup']:.2f}x, "
          f"spill {speedup:.2f}x, parity={parity})", file=sys.stderr)
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
         reps=args.reps if args.reps is not None else (1 if args.smoke else 3),
         out_path=args.out)
