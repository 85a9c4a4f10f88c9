"""Paper Fig. 8 — fine-grained resource sharing: the query co-runs with
low-priority, delay-tolerant background function chains (XFaaS-style).

Reports CPU allocation rates with and without background work, and verifies
the query's completion is not hurt (priority arbitration through the real
GlobalController). The query's shuffle phases leave CPU troughs that the
backfill fills — the paper's Fig. 8 effect.
"""

from __future__ import annotations

from repro.analytics import QueryStrategy, make_cluster, plan_query_tasks
from repro.analytics.simulator import SimTask
from repro.analytics.table import phantom
from repro.core.controllers import PrivateController

GB = 1 << 30


def run(with_background: bool, total_gb: float = 6.0, nodes: int = 6,
        bg_chains: int = 40, chain_len: int = 6):
    gc, sim = make_cluster(nodes)
    pc = PrivateController("query", gc, priority=10)
    fact = phantom("A", int(total_gb * 0.9 * GB), range(nodes))
    dim = phantom("B", int(total_gb * 0.05 * GB), range(2))
    plan_query_tasks(sim, pc, fact, dim, QueryStrategy("dynamic"))
    if with_background:
        for c in range(bg_chains):
            prev = None
            for i in range(chain_len):
                name = f"bg/{c}/{i}"
                sim.submit(SimTask(name, "background", 0.2, priority=0,
                                   deps=(prev,) if prev else ()))
                prev = name
    out = sim.run()
    query_t = out["completion"]["query"]
    alloc = out["allocation"].allocation_rate(0.0, query_t)
    return query_t, alloc, out


def main(rows: list | None = None):
    own = rows is None
    rows = [] if own else rows
    solo_t, solo_alloc, _ = run(False)
    shared_t, shared_alloc, _ = run(True)
    rows.append(("fig8/query_solo", solo_t * 1e6, solo_alloc))
    rows.append(("fig8/query_with_background", shared_t * 1e6, shared_alloc))
    rows.append(("fig8/allocation_gain", 0.0, shared_alloc - solo_alloc))
    rows.append(("fig8/query_slowdown", 0.0,
                 shared_t / max(solo_t, 1e-9)))
    if own:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived:.3f}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
