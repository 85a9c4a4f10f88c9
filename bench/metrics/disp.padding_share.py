"""Share of the rows dispatched to shape-class-padded kernels that were
padding: (padded - actual) / padded, in percent, from the invocation
records' ``rows_actual`` and ``rows_padded``."""


def read(run):
    padded = sum(q.rows_padded for q in run.queries)
    actual = sum(q.rows_actual for q in run.queries)
    if padded <= 0:
        return None
    return 100.0 * (padded - actual) / padded
