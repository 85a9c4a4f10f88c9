"""Function invocations per query, retries included (the runtime's
``MetricsSink`` records)."""


def read(run):
    if not run.queries:
        return None
    return sum(q.invocations for q in run.queries) / len(run.queries)
