"""Reduction of a profiler trace to device busy time, idle gaps and kernel
device time.

Two layers. ``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain records: the device's operation events, and the host markers
the harness drops at the edges of the traced window. Everything else works
on those records alone, so the tests check it on a small recorded trace
without a chip.

Clock: ``ProfileData`` gives every event's start in nanoseconds from the
start of the trace. The harness opens a ``TraceAnnotation`` named
``WINDOW_START`` at a ``time.perf_counter()`` it notes, and one named
``WINDOW_END`` at the close; their starts tie the trace's clock to the
host's, which is the clock of the program's spans.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW_START = "bench/window_start"
WINDOW_END = "bench/window_end"
# the line of a TPU plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""           # the program (``jit_...``) it ran in

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """The device's events (per chip), its program (module) events, and
    the host markers."""

    ops: dict = field(default_factory=dict)       # plane -> [Event]
    modules: dict = field(default_factory=dict)   # plane -> [Event]
    markers: dict = field(default_factory=dict)   # name -> start_ns


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def op_name(hlo_text: str) -> str:
    """``%fusion.10`` of ``%fusion.10 = s32[...] fusion(...)``: an XLA Ops
    event is named by the whole HLO instruction."""
    return hlo_text.split(" = ", 1)[0].strip()


def module_name(name: str) -> str:
    """``jit_f`` of ``jit_f(5123192737424326279)``."""
    return name.split("(", 1)[0]


def _attach_modules(ops: list, modules: list) -> None:
    """Give each operation the name of the program (module) whose device
    interval holds its start: the TPU's op events carry no module."""
    modules = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i >= 0 and op.start_ns < modules[i].end_ns:
            op.module = modules[i].name


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops.setdefault(plane.name, []).extend(
                        Event(op_name(e.name), float(e.start_ns),
                              float(e.duration_ns)) for e in line.events)
                elif line.name == MODULES_LINE:
                    tr.modules.setdefault(plane.name, []).extend(
                        Event(module_name(e.name), float(e.start_ns),
                              float(e.duration_ns)) for e in line.events)
            if plane.name in tr.ops:
                _attach_modules(tr.ops[plane.name],
                                tr.modules.get(plane.name, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (WINDOW_START, WINDOW_END):
                        tr.markers[e.name] = float(e.start_ns)
    return tr


def merged(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clipped(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_ns(tr: Trace) -> tuple[float, float] | None:
    """The traced window on the trace's clock, from the host markers."""
    if WINDOW_START not in tr.markers or WINDOW_END not in tr.markers:
        return None
    return tr.markers[WINDOW_START], tr.markers[WINDOW_END]


def busy_ns(tr: Trace, lo: float, hi: float) -> list[float]:
    """Per chip: nanoseconds inside ``[lo, hi]`` in which an operation ran."""
    return [sum(e - s for s, e in clipped(
                merged((ev.start_ns, ev.end_ns) for ev in evs), lo, hi))
            for _, evs in sorted(tr.ops.items())]


def idle_gaps(tr: Trace, lo: float, hi: float) -> list[tuple[float, float]]:
    """Gaps in ``[lo, hi]`` in which no operation ran on the first chip,
    longest first."""
    planes = sorted(tr.ops)
    if not planes:
        return [(lo, hi)]
    busy = clipped(merged((ev.start_ns, ev.end_ns)
                          for ev in tr.ops[planes[0]]), lo, hi)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10,
            ) -> list[tuple[str, float]]:
    """The ``k`` operation names that took most device seconds in the
    window (first chip), as ``(name, seconds)``."""
    planes = sorted(tr.ops)
    if not planes:
        return []
    tot: dict[str, float] = {}
    for ev in tr.ops[planes[0]]:
        for s, e in clipped([(ev.start_ns, ev.end_ns)], lo, hi):
            label = f"{ev.module}/{ev.name}" if ev.module else ev.name
            tot[label] = tot.get(label, 0.0) + (e - s) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]
