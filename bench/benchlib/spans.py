"""Per-query summaries of the program's spans, taken after each query (or
wave) of a traced run, before the tracer's ring buffer is cleared for the
next one."""

from __future__ import annotations

from benchlib.cpath import critical_path


def summarize(spans, app: str) -> dict:
    """Critical-path phases and gate waits of one query's spans."""
    mine = [s for s in spans if s.trace == app]
    out = {"gate_wait_s": sum(s.seconds for s in mine
                              if s.cat == "wait" and s.name == "gate_wait")}
    cp = critical_path(mine, app)
    if cp is not None:
        out.update({f"cp_{k}": v for k, v in cp.breakdown.items()})
    return out


def kernel_calls(spans) -> list[tuple[str, dict]]:
    """``(name, attrs)`` of every ``kernel/*`` dispatch span."""
    return [(s.name, dict(s.attrs)) for s in spans if s.cat == "kernel"]


def host_label(spans, t: float) -> str:
    """What the host was doing at ``t`` (perf_counter seconds): the
    category and first name component of the innermost span open then."""
    open_ = [s for s in spans if s.start <= t <= s.end]
    if not open_:
        return "none"
    s = max(open_, key=lambda s: s.start)
    return f"{s.cat}:{s.name.split('/')[0]}"
