"""Readings of the program's planner and function-body spans.

The harness clears the program's tracer before each unit of a traced run,
so when the metrics are read the tracer's buffer holds the spans of the
window's last unit: one query, or one wave of queries. These readings take
them from there, per query (trace id), and average over the unit's
queries.

A program that does not emit the spans a reading needs (no ``planner``
span; no ``xfer`` or ``sync`` span) gives ``None``; one that emits them
but spent no time in them gives 0.
"""

from __future__ import annotations

from benchlib.cpath import critical_path

BODY_CATS = ("xfer", "sync")


def last_unit_spans() -> list:
    """The program's spans of the window's last traced unit."""
    from repro.obs import get_tracer

    return get_tracer().spans()


def _invocations(spans) -> list:
    return [s for s in spans
            if s.cat == "invoker" and s.attrs.get("kind") == "invocation"]


def _queries(spans) -> list[str]:
    return sorted({s.trace for s in _invocations(spans)})


def _mean(vals):
    return sum(vals) / len(vals) if vals else None


def plan_seconds(spans):
    """Mean seconds per query inside the executor's ``plan/*`` spans (the
    planner's calls between stages, with the decisions bound in them)."""
    if not any(s.cat == "planner" for s in spans):
        return None
    return _mean([sum(s.seconds for s in spans
                      if s.trace == app and s.cat == "planner"
                      and s.name.startswith("plan/"))
                  for app in _queries(spans)])


def _union_inside(span, children: dict, cat: str) -> float:
    """Seconds of ``span`` covered by its descendants of category ``cat``."""
    found, todo = [], list(children.get(span.span_id, ()))
    while todo:
        s = todo.pop()
        if s.cat == cat:
            found.append((max(s.start, span.start), min(s.end, span.end)))
        todo.extend(children.get(s.span_id, ()))
    total, at = 0.0, span.start
    for lo, hi in sorted(found):
        lo = max(lo, at)
        if hi > lo:
            total += hi - lo
            at = hi
    return total


def critpath_inside(spans, cat: str):
    """Mean seconds per query that the invocations on the query's critical
    path (``benchlib/cpath.py``) spent inside spans of category ``cat``.
    Each step is scaled into the stretch of the makespan it extends, as
    the critical path's phases are, so this is a part of its ``compute``.
    """
    if not any(s.cat in BODY_CATS for s in spans):
        return None
    vals = []
    for app in _queries(spans):
        mine = [s for s in spans if s.trace == app]
        cp = critical_path(mine, app)
        if cp is None:
            continue
        children: dict = {}
        for s in mine:
            children.setdefault(s.parent_id, []).append(s)
        invs = {(s.name, s.start, s.end): s for s in _invocations(mine)}
        total, frontier = 0.0, min(s.start for s in mine)
        for step in sorted(cp.steps, key=lambda s: s.start):
            w = max(0.0, step.end - max(step.start, frontier))
            span = invs.get((step.name, step.start, step.end))
            if span is not None and step.seconds > 0:
                total += _union_inside(span, children, cat) \
                    * w / step.seconds
            frontier = max(frontier, step.end)
        vals.append(total)
    return _mean(vals)
