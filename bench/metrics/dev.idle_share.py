"""Share of the traced unit's window in which no operation ran on the
device, in percent: 1 - (union of device-operation intervals) / window,
from the profiler's trace."""

from benchlib import devtrace


def read(run):
    if run.trace is None or run.trace_window is None or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    busy = devtrace.busy_ns(run.trace, lo, hi)
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
