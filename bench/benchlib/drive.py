"""The one traffic generator: deployments, and the loops that drive them.

A traffic file (``bench/traffic/<name>.json``) is data only. Its keys:

* ``keys``       — the fact key law: ``{"law": "uniform"}`` or
                   ``{"law": "zipf", "s": 1.5}`` (see ``gen.py``);
* ``loop``       — ``"closed"``: one client, each query submitted when the
                   last one returned, through ``execute_query_runtime``;
                   ``"waves"``: every tenant's query submitted at once to
                   one ``QueryScheduler``, the next wave when the last one
                   has ended;
* ``strategies`` — join strategies; a closed loop uses the first, a wave
                   gives tenant ``i`` of wave ``w`` entry ``(i + w) % n``;
* ``priorities`` — tenant ``i``'s priority is entry ``i % n``;
* ``policy``     — the scheduler's admission policy (waves only);
* ``warmup_units`` — units run in set-up (default 1). A wave's plans
                   depend on how its queries overlap (the dynamic join
                   reads the free slots), so one wave may not meet every
                   shape the window will.

Each query of a run is a fresh application over the run's tables. Once it
has returned, its group sums are kept for the check that follows the
window, and its store and invocation records are released, so memory stays
flat however long the window runs.
"""

from __future__ import annotations

import time
import traceback
from dataclasses import dataclass, field

import numpy as np

from benchlib import gen


@dataclass
class Tenant:
    fact_parts: list            # device column dicts, one per node
    dim_parts: list
    fact: object = None         # the program's DistTable views of them
    dim: object = None


@dataclass
class QueryRec:
    """One query as the harness saw it. Times are ``time.perf_counter()``."""

    unit: int
    app: str
    tenant: int
    strategy: str
    priority: int
    submitted: float
    done: float = 0.0
    fact_rows: int = 0
    sums: object = None
    error: str | None = None
    decisions: tuple = ()
    fn_s: float = 0.0            # billed function-seconds, retries included
    invocations: int = 0
    rows_actual: int = 0
    rows_padded: int = 0
    spans: dict = field(default_factory=dict)   # traced runs only

    @property
    def latency(self) -> float:
        return self.done - self.submitted


class Deployment:
    """A configuration's tables, made on the device from the seed, and the
    shared runtime its queries run on."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax

        from repro.analytics.table import DistTable, Table
        from repro.core.controllers import GlobalController
        from repro.runtime import Runtime

        self.config, self.traffic = config, traffic
        fact_nodes = int(config["fact_nodes"])
        dim_nodes = int(config["dim_nodes"])
        plan = gen.fact_plan(int(config["fact_rows"]), fact_nodes,
                             int(config["dim_rows"]), traffic["keys"],
                             float(config["assumed"]["filter_pass_share"]))
        self.tenants = []
        for i in range(int(config.get("tenants", 1))):
            t = Tenant(gen.make_fact(seed + i, plan),
                       gen.make_dim(seed + i, int(config["dim_rows"]),
                                    dim_nodes, int(config["num_groups"])))
            t.fact = DistTable("A", {n: Table(dict(p)) for n, p
                                     in enumerate(t.fact_parts)})
            t.dim = DistTable("B", {n: Table(dict(p)) for n, p
                                    in enumerate(t.dim_parts)})
            self.tenants.append(t)
        jax.block_until_ready([t.fact_parts + t.dim_parts
                               for t in self.tenants])
        nodes = int(config["nodes"])
        gc = GlobalController({n: int(config["slots_per_node"])
                               for n in range(nodes)})
        self.runtime = Runtime(gc, invoker=config["invoker"],
                               max_workers=int(config["max_workers"]))
        # the host's clock at the moment the scheduler hands a finished
        # query's state back (its sums are captured just before)
        self.released: dict[str, float] = {}
        release = self.runtime.release

        def stamped_release(app: str) -> int:
            self.released[app] = time.perf_counter()
            return release(app)

        self.runtime.release = stamped_release

    @property
    def fact_rows(self) -> int:
        return int(self.config["fact_rows"])

    def run_unit(self, unit: int) -> list[QueryRec]:
        loop = self.traffic["loop"]
        if loop == "closed":
            return self._closed(unit)
        if loop == "waves":
            return self._wave(unit)
        raise ValueError(f"unknown loop {loop!r}")

    def _closed(self, unit: int) -> list[QueryRec]:
        from repro.analytics import (QueryStrategy, build_query_workflow,
                                     execute_query_runtime)

        t = self.tenants[0]
        name = self.traffic["strategies"][0]
        strategy = QueryStrategy(name)
        workflow = build_query_workflow(strategy)
        app = f"q{unit}"
        rec = QueryRec(unit, app, 0, name, int(self.traffic["priorities"][0]),
                       time.perf_counter(), fact_rows=self.fact_rows)
        try:
            sums, _ = execute_query_runtime(
                t.fact, t.dim, strategy, runtime=self.runtime, app=app,
                priority=rec.priority, workflow=workflow,
                num_groups=int(self.config["num_groups"]),
                pipeline=bool(self.config["pipeline"]))
            rec.sums = np.asarray(sums, np.float64)
        except Exception as e:  # noqa: BLE001 - a failed query is counted
            traceback.print_exc()
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = time.perf_counter()
        if workflow.last_run is not None:
            rec.decisions = tuple((n, d.func)
                                  for n, d in workflow.last_run.sequence)
        recs = self.runtime.metrics.for_app(app)
        rec.fn_s = sum(r.seconds for r in recs)
        rec.invocations = len(recs)
        rec.rows_actual = sum(r.rows_actual for r in recs)
        rec.rows_padded = sum(r.rows_padded for r in recs)
        self.runtime.release(app)
        self.released.pop(app, None)
        self.runtime.metrics.clear(app)
        return [rec]

    def _wave(self, unit: int) -> list[QueryRec]:
        from repro.runtime import QueryJob, QueryScheduler

        tr = self.traffic
        strategies, priorities = tr["strategies"], tr["priorities"]
        sched = QueryScheduler(self.runtime, policy=tr["policy"],
                               release_stores=True, compact_metrics=True)
        recs = []
        for i, t in enumerate(self.tenants):
            name = strategies[(i + unit) % len(strategies)]
            prio = int(priorities[i % len(priorities)])
            app = f"w{unit}t{i}"
            sched.submit(QueryJob(app, t.fact, t.dim, name, priority=prio,
                                  num_groups=int(self.config["num_groups"])))
            recs.append(QueryRec(unit, app, i, name, prio, 0.0,
                                 fact_rows=self.fact_rows))
        submitted = time.perf_counter()
        results = sched.run()
        for rec in recs:
            res = results[rec.app]
            rec.submitted = submitted
            rec.done = self.released.pop(rec.app, time.perf_counter())
            if res.ok:
                rec.sums = np.asarray(res.sums, np.float64)
            else:
                rec.error = f"{type(res.error).__name__}: {res.error}"
            rec.decisions = tuple((n, d.func) for n, d in res.decisions)
            stages = res.stages.values()
            rec.fn_s = sum(m.seconds for m in stages)
            rec.invocations = sum(m.invocations for m in stages)
            rec.rows_actual = sum(m.rows_actual for m in stages)
            rec.rows_padded = sum(m.rows_padded for m in stages)
        return recs
