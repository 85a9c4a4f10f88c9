"""One client: each query submitted when the last one returned, with the
traffic's first strategy and priority, on tenant 0."""

from __future__ import annotations

import time
import traceback

from benchlib.drive import QueryRec


def run_unit(dep, unit: int) -> list[QueryRec]:
    t = dep.tenants[0]
    name = dep.traffic["strategies"][0]
    prio = int(dep.traffic["priorities"][0])
    app = f"q{unit}"
    query = dep.app.closed(dep, t, app, name, prio)
    rec = QueryRec(unit, app, 0, name, prio, time.perf_counter(),
                   fact_rows=dep.input_rows)
    try:
        rec.answer = query.run()
    except Exception as e:  # noqa: BLE001 - a failed query is counted
        traceback.print_exc()
        rec.error = f"{type(e).__name__}: {e}"
    rec.done = time.perf_counter()
    rec.decisions = query.decisions()
    recs = dep.runtime.metrics.for_app(app)
    rec.fn_s = sum(r.seconds for r in recs)
    rec.invocations = len(recs)
    rec.rows_actual = sum(r.rows_actual for r in recs)
    rec.rows_padded = sum(r.rows_padded for r in recs)
    dep.runtime.release(app)
    dep.released.pop(app, None)
    dep.runtime.metrics.clear(app)
    return [rec]
