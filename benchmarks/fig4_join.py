"""Paper Fig. 4 — Join strategies under varying table size, cluster size,
and data skew.

(a,b) completion time + normalized cost: A = 400 MB vs B in 10..100 MB on a
      12-node cluster;
(c,d) the same at B = 80 MB across cluster sizes 4..20;
(e)   round-robin vs packing scheduling under uniform vs Pareto data.

Compute rates are calibrated from the real JAX operators; network is the
modeled 1.25 GB/s/NIC of c5.2xlarge. Prints ``name,us_per_call,derived`` CSV
rows (us_per_call = simulated completion in microseconds; derived =
normalized cost in slot-seconds).
"""

from __future__ import annotations

from repro.analytics import QueryStrategy, make_cluster, plan_query_tasks
from repro.analytics.decisions import ALPHA, scheduling_decision
from repro.analytics.simulator import SimTask, calibrated_rates
from repro.analytics.table import phantom
from repro.core.controllers import PrivateController
from repro.core.decisions import DataDist, DecisionContext

MB = 1 << 20


def run_join(nodes: int, a_mb: int, b_mb: int, method: str) -> tuple[float,
                                                                     float]:
    gc, sim = make_cluster(nodes)
    pc = PrivateController("query", gc, priority=10)
    fact = phantom("A", a_mb * MB, range(nodes))
    dim = phantom("B", b_mb * MB, range(min(2, nodes)))
    strat = QueryStrategy(
        "static_merge" if method == "merge" else "static_hash")
    plan_query_tasks(sim, pc, fact, dim, strat)
    out = sim.run()
    return out["completion"]["query"], out["cost_slot_seconds"]["query"]


def fig4_ab(rows: list):
    """Completion/cost vs small-table size (A=400MB, 12 nodes)."""
    for b_mb in (10, 20, 30, 50, 80, 100):
        for method in ("hash", "merge"):
            t, c = run_join(12, 400, b_mb, method)
            rows.append((f"fig4ab/{method}_join/B={b_mb}MB", t * 1e6, c))


def fig4_cd(rows: list):
    """Completion/cost vs cluster size (A=400MB, B=80MB)."""
    for nodes in (4, 8, 12, 16, 20):
        for method in ("hash", "merge"):
            t, c = run_join(nodes, 400, 80, method)
            rows.append((f"fig4cd/{method}_join/nodes={nodes}", t * 1e6, c))


def run_sched(policy: str, distribution: str, nodes: int = 8,
              total_mb: int = 800) -> float:
    """Fig. 4(e): process a distributed table under a scheduling policy."""
    gc, sim = make_cluster(nodes)
    rates = calibrated_rates()
    table = phantom("A", total_mb * MB, range(nodes),
                    distribution=distribution, seed=3)
    dist = table.data_dist()
    if policy == "decision":  # the scheduling decision node picks
        ctx = DecisionContext(data_dist={"A": dist},
                              node_status=gc.node_status())
        decision = scheduling_decision(ctx)
        policy_used = decision.schedule.policy
        placement = decision.schedule.place(decision.scale)
    else:
        policy_used = policy
        n_tasks = max(1, dist.size // ALPHA)
        if policy == "packing":
            heavy = sorted(dist.bytes_per_node,
                           key=lambda n: -dist.bytes_per_node[n])
            from repro.core.decisions import Schedule
            placement = Schedule("packing", tuple(heavy),
                                 slots_per_node=8).place(n_tasks)
        else:
            from repro.core.decisions import Schedule
            placement = Schedule("round-robin",
                                 tuple(range(nodes))).place(n_tasks)
    # tasks process equal shares; data lives where the skew put it
    n_tasks = len(placement)
    per = dist.size / n_tasks
    homes = sorted(dist.bytes_per_node, key=lambda n: -dist.bytes_per_node[n])
    # task i's input lives on the node holding that byte range
    acc, ranges = 0, []
    for node in homes:
        ranges.append((acc, acc + dist.bytes_per_node[node], node))
        acc += dist.bytes_per_node[node]
    for i, node in enumerate(placement):
        lo = i * per
        src = next((h for (a, b, h) in ranges if a <= lo < b), homes[0])
        transfers = {src: int(per)} if src != node else {}
        sim.submit(SimTask(f"t{i}", "app", per / rates["scan"], node=node,
                           priority=5, transfers=transfers))
    return sim.run()["completion"]["app"]


def fig4_e(rows: list):
    for distribution in ("uniform", "pareto"):
        for policy in ("round-robin", "packing", "decision"):
            t = run_sched(policy, distribution)
            rows.append((f"fig4e/{policy}/{distribution}", t * 1e6, 0.0))


def main(rows: list | None = None):
    own = rows is None
    rows = [] if own else rows
    fig4_ab(rows)
    fig4_cd(rows)
    fig4_e(rows)
    if own:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived:.3f}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
