"""Fault-tolerance benchmark: lineage recovery vs whole-query rerun, and
speculative execution vs straggler tails.

Section A (recovery): a seeded ``FaultPlan`` kills two invocations
(crash-before-commit on a scan, crash-after-write on the join), and evicts
one partition of the consumed ephemeral ``joined`` stage right as its
consumer first reads it. Each of the four join strategies runs twice under
the same plan:

* ``lineage`` — the executor heals the loss by re-executing only the lost
  partition's producer invocations (recursively through GC'd inputs; a
  store quota keeps consumed inputs sealed, so recovery stays shallow),
* ``rerun``   — the executor surfaces ``RecoveryError`` and the whole query
  re-executes from the base inputs (the Lambada-style baseline).

Reported per strategy: invocations re-executed beyond a fault-free run, and
wall time. Acceptance: lineage re-executes **< 50 %** of the invocations
the rerun baseline does (criteria in the summary).

Section B (speculation): one node straggles the fact scan by ``delay``
seconds; with a ``SpeculationPolicy`` installed the thread-pool invoker
launches a backup on another node once the invocation exceeds a p50
multiple (first completion wins). Reported: per-invocation completion p99
with and without speculation. Acceptance: speculation cuts the straggler
p99 below the injected delay.

    PYTHONPATH=src python benchmarks/bench_faults.py [--smoke]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

STRATEGIES = ("static_merge", "static_hash", "dynamic", "dynamic_fig6")
NODES, SLOTS_PER_NODE = 4, 8
ROWS, DIM_ROWS = 1 << 14, 1 << 10
SMOKE_ROWS, SMOKE_DIM_ROWS = 1 << 12, 1 << 9
DELAY, SMOKE_DELAY = 0.6, 0.25
OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_faults.json"
SMOKE_OUT_PATH = OUT_PATH.with_name("BENCH_faults_smoke.json")


def _recovery_plan():
    from repro.runtime import CrashFault, FaultPlan, StageLossFault

    return FaultPlan(
        crashes=[CrashFault("scan_fact", index=0, when="before"),
                 CrashFault("join", index=0, when="after")],
        losses=[StageLossFault("joined", partitions=(0,), on_read=1)])


def _make_runtime(quota: int | None = None):
    from repro.core.controllers import GlobalController
    from repro.runtime import Runtime

    gc = GlobalController({n: SLOTS_PER_NODE for n in range(NODES)})
    rt = Runtime(gc)
    if quota is not None:
        rt.store.set_quota("query", quota)
    return rt


def _bench_recovery(fd, dd, ref, strat: str) -> dict:
    import numpy as np

    from repro.analytics import QueryStrategy, execute_query_runtime
    from repro.runtime import FaultInjector, RecoveryError

    # fault-free execution count is the re-execution baseline
    got, rt = execute_query_runtime(fd, dd, QueryStrategy(strat),
                                    runtime=_make_runtime())
    np.testing.assert_allclose(got, ref, atol=1e-2)
    n_clean = len(rt.metrics.records)

    # lineage recovery (quota keeps consumed inputs sealed -> shallow heal)
    rt = _make_runtime(quota=1 << 30)
    FaultInjector(_recovery_plan()).install(rt)
    t0 = time.perf_counter()
    got, _ = execute_query_runtime(fd, dd, QueryStrategy(strat), runtime=rt)
    lineage_wall = time.perf_counter() - t0
    np.testing.assert_allclose(got, ref, atol=1e-2)
    assert rt.recoveries, "the loss was injected but never recovered"
    lineage_reexec = len(rt.metrics.records) - n_clean
    recovered = [list(ev.recovered) for ev in rt.recoveries]

    # whole-query rerun baseline: same plan, executor refuses to recompute
    rt = _make_runtime(quota=1 << 30)
    injector = FaultInjector(_recovery_plan()).install(rt)
    t0 = time.perf_counter()
    try:
        execute_query_runtime(fd, dd, QueryStrategy(strat), runtime=rt,
                              recovery="rerun")
        raise AssertionError("loss did not surface under rerun policy")
    except RecoveryError:
        pass
    rt.release("query")                      # tear down the failed attempt
    # the fault already fired; the rerun executes fault-free on the same
    # (still-armed but exhausted) injector — exactly once
    got, _ = execute_query_runtime(fd, dd, QueryStrategy(strat), runtime=rt)
    rerun_wall = time.perf_counter() - t0
    np.testing.assert_allclose(got, ref, atol=1e-2)
    rerun_reexec = len(rt.metrics.records) - n_clean
    assert injector.injected, "fault plan never fired"

    return {
        "clean_invocations": n_clean,
        "lineage_reexec": lineage_reexec,
        "rerun_reexec": rerun_reexec,
        "reexec_ratio": lineage_reexec / max(1, rerun_reexec),
        "lineage_wall_s": lineage_wall,
        "rerun_wall_s": rerun_wall,
        "recovered_stages": recovered,
    }


def _completion_p99(metrics, stage: str) -> float:
    """p99 over per-invocation completion times: for each invocation index
    the *first* successful copy counts (first-completion-wins)."""
    import numpy as np

    best: dict[str, float] = {}
    for r in metrics.records:
        if r.stage == stage and r.status == "ok":
            best[r.name] = min(best.get(r.name, float("inf")), r.seconds)
    return float(np.percentile(sorted(best.values()), 99))


def _bench_speculation(fd, dd, ref, delay: float) -> dict:
    import numpy as np

    from repro.analytics import QueryStrategy, execute_query_runtime
    from repro.core.controllers import GlobalController
    from repro.runtime import (
        FaultInjector,
        FaultPlan,
        MetricsSink,
        Runtime,
        ShuffleStore,
        SpeculationPolicy,
        StragglerFault,
        ThreadPoolInvoker,
    )

    out = {}
    for mode in ("no_speculation", "speculation"):
        plan = FaultPlan(stragglers=[StragglerFault(node=1, delay=delay,
                                                    stage="scan_fact")])
        gc = GlobalController({n: SLOTS_PER_NODE for n in range(NODES)})
        store, metrics = ShuffleStore(), MetricsSink()
        policy = SpeculationPolicy(multiple=3.0, floor=0.02,
                                   interval=0.01) \
            if mode == "speculation" else None
        invoker = ThreadPoolInvoker(gc, store, metrics, max_workers=8,
                                    speculation=policy)
        rt = Runtime(gc, invoker=invoker, store=store, metrics=metrics)
        FaultInjector(plan).install(rt)
        t0 = time.perf_counter()
        got, _ = execute_query_runtime(fd, dd, QueryStrategy("static_hash"),
                                       runtime=rt)
        wall = time.perf_counter() - t0
        np.testing.assert_allclose(got, ref, atol=1e-2)
        invoker.drain()
        assert sum(gc.used.values()) == 0, "slot leak"
        out[mode] = {
            "scan_p99_s": _completion_p99(metrics, "scan_fact"),
            "query_wall_s": wall,
            "backups_launched": len(invoker.speculations),
        }
    return out


def main(rows: list | None = None, smoke: bool = False,
         out_path: Path | str | None = None) -> dict:
    from repro.analytics import synth_query_tables

    own = rows is None
    rows = [] if own else rows
    if out_path is None:
        # smoke runs must not clobber the committed full-run artifact
        out_path = SMOKE_OUT_PATH if smoke else OUT_PATH
    n_rows, n_dim = (SMOKE_ROWS, SMOKE_DIM_ROWS) if smoke \
        else (ROWS, DIM_ROWS)
    delay = SMOKE_DELAY if smoke else DELAY
    fd, dd, ref = synth_query_tables(n_rows, n_dim, seed=17,
                                     fact_nodes=NODES, dim_nodes=[0, 1])

    from repro.obs import get_tracer, write_bench_artifacts

    recovery = {s: _bench_recovery(fd, dd, ref, s) for s in STRATEGIES}
    # speculation runs last with a fresh buffer: the exported artifact shows
    # the straggler, the speculate/* markers and the backup invocations
    get_tracer().clear()
    speculation = _bench_speculation(fd, dd, ref, delay)

    total_lineage = sum(r["lineage_reexec"] for r in recovery.values())
    total_rerun = sum(r["rerun_reexec"] for r in recovery.values())
    frac = total_lineage / max(1, total_rerun)
    p99_no = speculation["no_speculation"]["scan_p99_s"]
    p99_spec = speculation["speculation"]["scan_p99_s"]
    summary = {
        "lineage_reexec_frac_vs_rerun": frac,
        "straggler_p99_no_spec_s": p99_no,
        "straggler_p99_spec_s": p99_spec,
        "straggler_p99_speedup": p99_no / max(1e-9, p99_spec),
        "criteria": {
            "lineage_reexecutes_under_half_of_rerun": frac < 0.5,
            "speculation_cuts_straggler_p99": p99_spec < p99_no,
        },
    }
    report = {
        "benchmark": "faults_lineage_recovery_and_speculation",
        "config": {"rows": n_rows, "dim_rows": n_dim, "nodes": NODES,
                   "slots_per_node": SLOTS_PER_NODE,
                   "straggler_delay_s": delay,
                   "strategies": list(STRATEGIES), "smoke": smoke},
        "recovery": recovery,
        "speculation": speculation,
        "summary": summary,
        # trace of the speculation runs + the query's critical path
        "observability": write_bench_artifacts(out_path, apps=["query"]),
    }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")

    for strat in STRATEGIES:
        r = recovery[strat]
        rows.append((f"faults/{strat}/lineage_reexec",
                     r["lineage_wall_s"] * 1e6,
                     f"{r['lineage_reexec']}v{r['rerun_reexec']}"))
    rows.append(("faults/lineage_reexec_frac", 0.0, round(frac, 3)))
    rows.append(("faults/straggler_p99_speedup", 0.0,
                 round(summary["straggler_p99_speedup"], 2)))
    if own:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
    print(f"# wrote {out_path}: lineage re-executes {total_lineage} vs "
          f"rerun {total_rerun} invocations ({frac:.0%}); straggler p99 "
          f"{p99_no:.2f}s -> {p99_spec:.3f}s with speculation",
          file=sys.stderr)
    return report


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny tables, short straggler delay (CI: exercises "
                         "injection/recovery paths, no perf claim)")
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_faults.json, or "
                         "BENCH_faults_smoke.json under --smoke)")
    args = ap.parse_args()
    main(smoke=args.smoke, out_path=args.out)
