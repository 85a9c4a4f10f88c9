"""Observability substrate: spans, decision audit, critical path, export.

``repro.obs`` is dependency-free (pure stdlib) so every runtime layer —
scheduler, executor, invoker, store, kernels, decision nodes — can import
it without cycles. The global ``Tracer`` (``get_tracer``) records a
parent/child span DAG per query (trace id == app name) into a bounded ring
buffer; the global ``DecisionAuditLog`` (``get_audit_log``) records every
``DecisionNode`` binding with the context snapshot it saw. On top:
``critical_path`` walks the span DAG to the chain bounding a query's
makespan, and ``to_chrome_trace``/``write_chrome_trace`` emit a
Perfetto-loadable timeline. ``Tracer.annotate`` mirrors every context
span onto an external timeline; ``repro.runtime`` points it at the JAX
profiler, so this package never imports jax.
"""

from repro.obs.audit import (
    AuditEntry,
    DecisionAuditLog,
    bound_app,
    get_audit_log,
    set_audit_log,
)
from repro.obs.critical_path import CriticalPath, PathStep, critical_path
from repro.obs.export import (
    to_chrome_trace,
    validate_chrome_trace,
    write_bench_artifacts,
    write_chrome_trace,
)
from repro.obs.tracer import Span, Tracer, get_tracer, set_tracer

__all__ = [
    "AuditEntry",
    "CriticalPath",
    "DecisionAuditLog",
    "PathStep",
    "Span",
    "Tracer",
    "bound_app",
    "critical_path",
    "get_audit_log",
    "get_tracer",
    "set_audit_log",
    "set_tracer",
    "to_chrome_trace",
    "validate_chrome_trace",
    "write_bench_artifacts",
    "write_chrome_trace",
]
