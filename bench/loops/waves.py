"""Waves: every tenant's query submitted at once to one
``QueryScheduler`` under the traffic's policy; the next wave when the last
one has ended. Tenant ``i`` of wave ``w`` takes strategy ``(i + w) % n``
and priority ``i % n``."""

from __future__ import annotations

import time

from benchlib.drive import QueryRec


def run_unit(dep, unit: int) -> list[QueryRec]:
    from repro.runtime import QueryScheduler

    tr = dep.traffic
    strategies, priorities = tr["strategies"], tr["priorities"]
    sched = QueryScheduler(dep.runtime, policy=tr["policy"],
                           release_stores=True, compact_metrics=True)
    recs = []
    for i, t in enumerate(dep.tenants):
        name = strategies[(i + unit) % len(strategies)]
        prio = int(priorities[i % len(priorities)])
        app = f"w{unit}t{i}"
        sched.submit(dep.app.job(dep, t, app, name, prio))
        recs.append(QueryRec(unit, app, i, name, prio, 0.0,
                             fact_rows=dep.input_rows))
    submitted = time.perf_counter()
    results = sched.run()
    for rec in recs:
        res = results[rec.app]
        rec.submitted = submitted
        rec.done = dep.released.pop(rec.app, time.perf_counter())
        if res.ok:
            rec.answer = dep.app.answer(res.sums)
        else:
            rec.error = f"{type(res.error).__name__}: {res.error}"
        rec.decisions = tuple((n, d.func) for n, d in res.decisions)
        stages = res.stages.values()
        rec.fn_s = sum(m.seconds for m in stages)
        rec.invocations = sum(m.invocations for m in stages)
        rec.rows_actual = sum(m.rows_actual for m in stages)
        rec.rows_padded = sum(m.rows_padded for m in stages)
    return recs
