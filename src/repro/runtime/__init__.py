"""Executable serverless function runtime (paper's shared substrate).

The control plane (``repro.core``) decides *func/scale/schedule*; this
package executes those decisions for real: stateless function instances
(``invoker``) run registered partitioned-analytics functions (``functions``)
over an ephemeral externalized-state store (``store``), orchestrated as a
stage DAG (``executor``), with per-invocation metrics (``metrics``) folded
back into the decision workflows and optionally replayed into the cluster
simulator so both data planes share one plan.

Importing this package mirrors the tracer's context spans onto the JAX
profiler's host timeline (``Tracer.annotate``): under
``jax.profiler.trace`` the program's spans land on the trace's clock,
beside the device's operations. Outside a profiling session an annotation
costs about a microsecond; a disabled tracer never enters it.
"""

import jax.profiler as _jax_profiler

from repro.obs.tracer import Tracer as _Tracer
from repro.runtime.storage import (  # noqa: F401
    DiskBackend,
    MemoryBackend,
    ObjectStoreBackend,
    StorageBackend,
    make_backend,
)
from repro.runtime.store import (  # noqa: F401
    Blob,
    QuotaExceededError,
    ShuffleStore,
    StageLostError,
)
from repro.runtime.faults import (  # noqa: F401
    CrashFault,
    FaultInjector,
    FaultPlan,
    InjectedCrashError,
    InjectedFault,
    RecoveryError,
    SpeculationPolicy,
    StageLossFault,
    StragglerFault,
    WorkerKilledError,
    WorkerKillFault,
)
from repro.runtime.lineage import (  # noqa: F401
    LineageLog,
    RecoveryEvent,
    StageLineage,
    expected_recovery,
)
from repro.runtime.metrics import (  # noqa: F401
    InvocationRecord,
    MetricsSink,
    StageMetrics,
)
from repro.runtime.invoker import (  # noqa: F401
    FnContext,
    InlineInvoker,
    Invocation,
    InvocationError,
    Invoker,
    SlotGate,
    ThreadPoolInvoker,
)
from repro.runtime.functions import FUNCTIONS, register  # noqa: F401
from repro.runtime.workers import (  # noqa: F401
    ProcessPoolInvoker,
    WorkerPool,
)
from repro.runtime.executor import (  # noqa: F401
    DAGExecutor,
    Runtime,
    RuntimeStage,
    StagePlanner,
)
from repro.runtime.scheduler import (  # noqa: F401
    FairShareGate,
    GateTimeoutError,
    QueryJob,
    QueryResult,
    QueryScheduler,
)


def _profiler_annotation(label: str, attrs: dict):
    """A ``TraceAnnotation`` for one span; a function body's span carries
    the function's name as the event's ``func`` stat."""
    func = attrs.get("func")
    if func is None:
        return _jax_profiler.TraceAnnotation(label)
    return _jax_profiler.TraceAnnotation(label, func=str(func))


_Tracer.annotate = staticmethod(_profiler_annotation)
