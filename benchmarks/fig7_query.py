"""Paper Fig. 7 — TPC-DS sub-query completion under S-M / S-H / DYN.

Two MapReduce phases + Join on a 6-node cluster, inputs 2/4/6 GB (90% fact,
5% dim as in the paper's scale ratio). DYN is the cost-model decision node
(with the literal Fig. 6 threshold node reported alongside).
"""

from __future__ import annotations

from repro.analytics import QueryStrategy, make_cluster, plan_query_tasks
from repro.analytics.table import phantom
from repro.core.controllers import PrivateController

GB = 1 << 30
STRATEGIES = ("static_merge", "static_hash", "dynamic", "dynamic_fig6")


def run_query(strategy: str, total_gb: float, nodes: int = 6):
    gc, sim = make_cluster(nodes)
    pc = PrivateController("query", gc, priority=10)
    fact = phantom("A", int(total_gb * 0.9 * GB), range(nodes))
    dim = phantom("B", int(total_gb * 0.05 * GB), range(2))
    plan_query_tasks(sim, pc, fact, dim, QueryStrategy(strategy))
    out = sim.run()
    return out["completion"]["query"], out["cost_slot_seconds"]["query"]


def main(rows: list | None = None):
    own = rows is None
    rows = [] if own else rows
    for gb in (2, 4, 6):
        results = {}
        for strat in STRATEGIES:
            t, c = run_query(strat, gb)
            results[strat] = t
            rows.append((f"fig7/{strat}/{gb}GB", t * 1e6, c))
        best_static = min(results["static_merge"], results["static_hash"])
        rows.append((f"fig7/dyn_vs_best_static/{gb}GB",
                     results["dynamic"] * 1e6,
                     results["dynamic"] / best_static))
    if own:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived:.3f}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
