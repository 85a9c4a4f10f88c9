"""Metric readers, found by name.

Each metric of ``BENCHMARK.json`` has a file ``bench/metrics/<name>.py``
with one function, ``read(run) -> float | None``. ``run`` is the
``RunView`` below. A reader that finds nothing to read returns ``None`` and
the harness leaves the metric out of the line; a reader never returns 0
for a share of a roofline or of a peak it could not measure.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from benchlib.cell import load_module


@dataclass
class RunView:
    """What one run measured, as the readers see it."""

    queries: list                       # QueryRec of every window query
    window_s: float
    setup_s: float
    kernel_calls: list = field(default_factory=list)   # (name, attrs)
    trace: object = None                # devtrace.Trace of the traced unit
    trace_window: tuple | None = None   # (lo_ns, hi_ns) on the trace clock
    peaks: dict | None = None


def load_reader(name: str):
    return load_module("metrics", name).read


def read_all(metrics: list, run: RunView) -> dict:
    """``{name: {"value": v, "unit": u}}`` for every metric whose reader
    found something."""
    out = {}
    for m in metrics:
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
