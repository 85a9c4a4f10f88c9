"""A partition kernel's share of its roofline in the traced unit.

The least time is the sum, over the kernel's dispatches in the unit (the
program's ``kernel/*`` spans, which record each call's rows and buckets),
of what the algorithm needs (``cost.py``) at the chip's peaks. The time
taken is the device time of the kernel's own operations in the profiler's
trace: the events of the ``pallas_call`` inside the kernel's program
(``module``), found by the operation's name. A reader returns ``None``
where the unit dispatched no such kernel, or where the trace holds another
number of the kernel's events than the spans hold dispatches (the pairing
would then be unsound).
"""

from __future__ import annotations

from benchlib import cost as cost_mod


def kernel_events(run, module: str, op_prefix: str) -> list:
    """The kernel's device events in the traced window (first chip): the
    operations named ``op_prefix...`` (the ``pallas_call``) of the program
    ``module``."""
    if run.trace is None or run.trace_window is None or not run.trace.ops:
        return []
    lo, hi = run.trace_window
    plane = sorted(run.trace.ops)[0]
    return [e for e in run.trace.ops[plane]
            if e.module == module and e.name.startswith(op_prefix)
            and e.end_ns > lo and e.start_ns < hi]


def roofline_share(run, span_name: str, cost_fn, module: str,
                   op_prefix: str, extra_buckets: int = 0):
    """Percent of the roofline the kernel reached, or ``None``."""
    calls = [a for n, a in run.kernel_calls
             if n == span_name and a.get("path") == "pallas"]
    events = kernel_events(run, module, op_prefix)
    if not calls or not events or len(events) != len(calls) \
            or run.peaks is None:
        return None
    least = sum(cost_mod.roofline_seconds(
        cost_fn(int(a["rows"]), int(a["buckets"]) + extra_buckets),
        run.peaks) for a in calls)
    taken = sum(e.dur_ns for e in events) * 1e-9
    return 100.0 * least / taken if taken > 0 else None
