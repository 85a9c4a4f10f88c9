"""TPC-DS Q42/Q52's shape: ``store_sales`` joined with ``item``, a filter,
``SUM(v0 * v1)`` by the item's category (``benchlib/oracle.py``).

Each tenant has its own fact and item tables, made on the device from
``seed + i`` (``benchlib/gen.py``). A closed loop runs a query through
``execute_query_runtime``; a wave submits it as a ``QueryJob``. The answer
is the per-group sums in float64, compared by the relative error of the
worst group.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchlib import gen, oracle


@dataclass
class Tenant:
    fact_parts: list            # device column dicts, one per node
    dim_parts: list
    fact: object = None         # the program's DistTable views of them
    dim: object = None


def make_tenants(config: dict, traffic: dict, seed: int) -> list[Tenant]:
    import jax

    from repro.analytics.table import DistTable, Table

    fact_nodes = int(config["fact_nodes"])
    dim_nodes = int(config["dim_nodes"])
    plan = gen.fact_plan(int(config["fact_rows"]), fact_nodes,
                         int(config["dim_rows"]), traffic["keys"],
                         float(config["assumed"]["filter_pass_share"]))
    tenants = []
    for i in range(int(config.get("tenants", 1))):
        t = Tenant(gen.make_fact(seed + i, plan),
                   gen.make_dim(seed + i, int(config["dim_rows"]),
                                dim_nodes, int(config["num_groups"])))
        t.fact = DistTable("A", {n: Table(dict(p)) for n, p
                                 in enumerate(t.fact_parts)})
        t.dim = DistTable("B", {n: Table(dict(p)) for n, p
                                in enumerate(t.dim_parts)})
        tenants.append(t)
    jax.block_until_ready([t.fact_parts + t.dim_parts for t in tenants])
    return tenants


def input_rows(config: dict) -> int:
    return int(config["fact_rows"])


class ClosedQuery:
    """One query for a closed loop, its workflow built before it is
    submitted."""

    def __init__(self, dep, tenant: Tenant, app: str, strategy: str,
                 priority: int):
        from repro.analytics import QueryStrategy, build_query_workflow

        self.dep, self.tenant, self.app = dep, tenant, app
        self.priority = priority
        self.strategy = QueryStrategy(strategy)
        self.workflow = build_query_workflow(self.strategy)

    def run(self) -> np.ndarray:
        from repro.analytics import execute_query_runtime

        config = self.dep.config
        sums, _ = execute_query_runtime(
            self.tenant.fact, self.tenant.dim, self.strategy,
            runtime=self.dep.runtime, app=self.app, priority=self.priority,
            workflow=self.workflow, num_groups=int(config["num_groups"]),
            pipeline=bool(config["pipeline"]))
        return answer(sums)

    def decisions(self) -> tuple:
        if self.workflow.last_run is None:
            return ()
        return tuple((n, d.func) for n, d in self.workflow.last_run.sequence)


closed = ClosedQuery


def job(dep, tenant: Tenant, app: str, strategy: str, priority: int):
    from repro.runtime import QueryJob

    return QueryJob(app, tenant.fact, tenant.dim, strategy, priority=priority,
                    num_groups=int(dep.config["num_groups"]))


def answer(raw) -> np.ndarray:
    return np.asarray(raw, np.float64)


def _host_tables(tenant: Tenant) -> tuple[dict, dict]:
    return (oracle.host_columns(tenant.fact_parts, ("key", "v0", "v1")),
            oracle.host_columns(tenant.dim_parts, ("key", "cat")))


def reference(config: dict, tenant: Tenant) -> np.ndarray:
    return oracle.reference_sums(*_host_tables(tenant),
                                 int(config["num_groups"]))


def control(config: dict, tenant: Tenant) -> np.ndarray:
    return oracle.control_sums(*_host_tables(tenant),
                               int(config["num_groups"]))


error = oracle.relative_error
