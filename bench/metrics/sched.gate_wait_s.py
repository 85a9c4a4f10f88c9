"""Mean seconds per query that the query's invocations spent blocked on
the scheduler's fair-share gate (``gate_wait`` spans of the traced run)."""


def read(run):
    waits = [q.spans["gate_wait_s"] for q in run.queries if q.spans]
    return sum(waits) / len(waits) if waits else None
