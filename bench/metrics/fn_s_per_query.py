"""Billed function-seconds per completed query: the sum of every
invocation's duration, preempted and failed attempts included, as the
runtime's ``MetricsSink`` records them, over the window's queries. It is
what a serverless user pays for."""


def read(run):
    if not run.queries:
        return None
    return sum(q.fn_s for q in run.queries) / len(run.queries)
