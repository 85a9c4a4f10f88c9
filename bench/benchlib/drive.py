"""The one traffic generator: deployments, and the loops that drive them.

A traffic file (``bench/traffic/<name>.json``) is data only. Its keys:

* ``keys``       — the fact key law: ``{"law": "uniform"}`` or
                   ``{"law": "zipf", "s": 1.5}`` (see ``gen.py``);
* ``loop``       — the loop ``bench/loops/<loop>.py``. ``"closed"``: one
                   client, each query submitted when the last one
                   returned; ``"waves"``: every tenant's query submitted at
                   once to one ``QueryScheduler``, the next wave when the
                   last one has ended;
* ``strategies`` — join strategies; a closed loop uses the first, a wave
                   gives tenant ``i`` of wave ``w`` entry ``(i + w) % n``;
* ``priorities`` — tenant ``i``'s priority is entry ``i % n``;
* ``policy``     — the scheduler's admission policy (waves only);
* ``warmup_units`` — units run in set-up (default 1). A wave's plans
                   depend on how its queries overlap (the dynamic join
                   reads the free slots), so one wave may not meet every
                   shape the window will.

A configuration's ``app`` key names its application,
``bench/apps/<app>.py`` (default ``tpcds_join_agg``). An app module
provides:

* ``make_tenants(config, traffic, seed)`` — each tenant's tables, made on
  the device from the seed and ready;
* ``input_rows(config)`` — the input rows of one query (``QueryRec``);
* ``closed(dep, tenant, app, strategy, priority)`` — one query of a closed
  loop, not yet run: ``run()`` returns its answer on the host, and
  ``decisions()`` the ``(stage, func)`` plan it bound, also after a failure;
* ``job(dep, tenant, app, strategy, priority)`` — the same query as a job
  for a ``QueryScheduler``, and ``answer(raw)`` — a job's answer on the host;
* ``reference(config, tenant)`` — the float64 answer, from the tenant's
  tables alone, importing nothing of the program;
* ``control(config, tenant)`` — the reference one precision below the
  configuration's, which ``bench/calibrate.py`` reads;
* ``error(got, ref)`` — the number ``bench/run.py`` compares with the
  cell's ``rel_err`` limit (``inf`` for a missing answer).

A loop module provides ``run_unit(dep, unit) -> list[QueryRec]``: it asks
the app for each query or job and stamps the records.

Each query of a run is a fresh application over the run's tables. Once it
has returned, its answer is kept for the check that follows the window,
and its store and invocation records are released, so memory stays flat
however long the window runs.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from benchlib.cell import load_module

DEFAULT_APP = "tpcds_join_agg"


@dataclass
class QueryRec:
    """One query as the harness saw it. Times are ``time.perf_counter()``.
    ``fact_rows`` counts the input rows of the query, whatever its app
    (``rows_per_s`` reads it by this name)."""

    unit: int
    app: str
    tenant: int
    strategy: str
    priority: int
    submitted: float
    done: float = 0.0
    fact_rows: int = 0
    answer: object = None
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
    """A configuration's tenants, their tables made on the device from the
    seed by the configuration's app, and the shared runtime its queries
    run on."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.controllers import GlobalController
        from repro.runtime import Runtime

        self.config, self.traffic = config, traffic
        self.app = load_module("apps", config.get("app", DEFAULT_APP))
        self.loop = load_module("loops", traffic["loop"])
        self.tenants = self.app.make_tenants(config, traffic, seed)
        nodes = int(config["nodes"])
        gc = GlobalController({n: int(config["slots_per_node"])
                               for n in range(nodes)})
        self.runtime = Runtime(gc, invoker=config["invoker"],
                               max_workers=int(config["max_workers"]))
        # the host's clock at the moment the scheduler hands a finished
        # query's state back (its answer is captured just before)
        self.released: dict[str, float] = {}
        release = self.runtime.release

        def stamped_release(app: str) -> int:
            self.released[app] = time.perf_counter()
            return release(app)

        self.runtime.release = stamped_release

    @property
    def input_rows(self) -> int:
        return self.app.input_rows(self.config)

    def run_unit(self, unit: int) -> list[QueryRec]:
        return self.loop.run_unit(self, unit)
