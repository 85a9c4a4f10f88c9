"""The TPC-DS-like sub-query executed for real on the serverless runtime.

One decision workflow per query (scan → join → exchange → aggregate) drives
actual partitioned function invocations through the dependency-driven DAG
executor: the scan decision binds up front, the scans run (concurrently
under the ``threads`` invoker), and when the fact scan lands the planner
folds the observed post-filter distribution back into the workflow context
and late-binds the join/exchange/aggregate decisions — re-planning the
query mid-flight. The invocation trace is then replayed into ``ClusterSim``
so the simulated benchmarks and the real data plane share one plan.

    PYTHONPATH=src python examples/runtime_query.py
"""

import jax.numpy as jnp
import numpy as np

from repro.analytics import (
    QueryStrategy,
    Table,
    build_query_workflow,
    execute_query_runtime,
    make_cluster,
    reference_query_numpy,
    synth_table,
)
from repro.analytics.query import ORACLE_RTOL, oracle_relative_error
from repro.analytics.simulator import calibrated_rates
from repro.analytics.table import distribute


def main():
    rows, dim_rows, keyspace = 1 << 15, 1 << 10, 1 << 12
    fact = synth_table("fact", rows, keyspace, seed=1)
    dimc = synth_table("dim", dim_rows, keyspace, seed=2, unique_keys=True)
    dim = Table({**dimc.columns,
                 "cat": jnp.arange(dim_rows, dtype=jnp.int32) % 64})
    ref = reference_query_numpy(fact, dim)

    fact_dist = distribute(fact, range(6), "A")
    dim_dist = distribute(dim, range(2), "B")

    for strat in ("static_hash", "static_merge", "dynamic"):
        wf = build_query_workflow(QueryStrategy(strat))
        got, runtime = execute_query_runtime(
            fact_dist, dim_dist, QueryStrategy(strat), workflow=wf,
            invoker="threads")
        err = oracle_relative_error(got, ref)
        print(f"\n=== strategy {strat}: group-sum relative err vs numpy "
              f"oracle {err:.2e} (limit {ORACLE_RTOL:g}) ===")
        assert err <= ORACLE_RTOL, strat
        run = wf.last_run
        print("decision sequence (bound in order, join late-bound on the "
              "observed post-filter scan output):")
        for name, d in run.sequence:
            print(f"  {name:10s} -> func={d.func:12s} scale={d.scale:3d} "
                  f"schedule={d.schedule.policy}")
        scanned = run.ctx.data_dist.get("A_scanned")
        print(f"observed post-filter fact side: {scanned.size} bytes over "
              f"{len(scanned.loc)} nodes (raw input {fact_dist.nbytes})")
        print(runtime.metrics.format_table("query"))
        store = runtime.store
        print(f"shuffle store: {store.cross_node_bytes} cross-node bytes, "
              f"{sum(store.written_bytes.values())} written, "
              f"{sum(store.resident_bytes.values())} still resident")

        # one plan, two data planes: replay the trace into the simulator
        gc2, sim = make_cluster(6)
        n = runtime.replay_into(sim, rates=calibrated_rates())
        out = sim.run()
        print(f"trace replay: {n} invocations -> simulated completion "
              f"{out['completion']['query'] * 1e3:.2f} ms")

        # observability: the span DAG's critical path and the audit log's
        # record of every decision binding (diffable vs run.sequence above)
        from repro.obs import critical_path, get_audit_log, get_tracer
        cp = critical_path(get_tracer().spans(), app="query")
        if cp is not None:
            print(cp.format())
        audited = get_audit_log().sequence("query",
                                           nodes=[s for s, _ in run.sequence])
        print(f"audit log: {audited} "
              f"{'==' if audited == [(s, d.func) for s, d in run.sequence] else '!='} "
              f"run.sequence")
        get_tracer().clear()      # fresh trace + audit buffers per strategy
        get_audit_log().clear()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
