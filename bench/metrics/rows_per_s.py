"""Fact rows that the window's completed queries scanned and joined, over
the window's whole length (host clock)."""


def read(run):
    if not run.queries or run.window_s <= 0:
        return None
    return sum(q.fact_rows for q in run.queries) / run.window_s
