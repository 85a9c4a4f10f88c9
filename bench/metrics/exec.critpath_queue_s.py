"""Mean seconds per query that the query's critical path spent between
invocations: scheduling and dispatch latency of the DAG executor (critical
path over the program's spans, ``benchlib/cpath.py``)."""


def read(run):
    vals = [q.spans["cp_queue"] for q in run.queries if "cp_queue" in q.spans]
    return sum(vals) / len(vals) if vals else None
