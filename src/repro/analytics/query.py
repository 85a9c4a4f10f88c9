"""TPC-DS-like sub-query (paper §6): two MapReduce phases + a Join phase.

    Q: SELECT d.cat, SUM(f.v0 * f.v1)
       FROM fact f JOIN dim d ON f.key = d.key
       WHERE f.v0 > 0
       GROUP BY d.cat

Execution under Proteus: one decision workflow per query (scan → join →
exchange → aggregate decision nodes, see ``repro.analytics.planner``) drives
both data planes. Decisions are **late-bound**: the join node is evaluated
only after the scan stage's runtime feedback — including the observed
post-filter fact distribution — has been folded into the context, so a
selective filter can flip the join variant mid-query. On the serverless
runtime the dependency-driven DAG executor interleaves decision evaluation
with stage completion through ``AdaptiveQueryPlan``; on the cluster
simulator the same workflow binds the same decision sequence against an
estimated scan output. ``execute_query_runtime`` and ``plan_query_tasks``
are thin wrappers over that shared machinery; ``execute_query_jax`` runs
the logical plan in-process for correctness tests against a numpy oracle.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.analytics import operators as ops
from repro.analytics.decisions import ALPHA
from repro.analytics.planner import (
    AdaptiveQueryPlan,
    plan_query_with_workflow,
    resolve_query_workflow as _resolve_workflow,
    scan_stages,
    tail_stages,
)
from repro.analytics.simulator import ClusterSim
from repro.analytics.table import DistTable, Table, distribute, synth_table
from repro.core.controllers import GlobalController, PrivateController
from repro.core.decisions import (
    DataDist,
    Decision,
    DecisionContext,
    DecisionWorkflow,
    Schedule,
)

def synth_query_tables(rows: int = 4096, dim_rows: int = 512,
                       keyspace: int | None = None, seed: int = 1,
                       fact_nodes=4, dim_nodes=2, num_groups: int = 64,
                       zipf: float = 0.0, heavy_hitters: int = 0,
                       ) -> tuple[DistTable, DistTable, np.ndarray]:
    """Synthetic fact/dim pair + numpy oracle for the TPC-DS-like sub-query.

    The one workload builder shared by benchmarks, examples and tests (the
    ``cat`` cardinality must match ``num_groups`` — keeping it here stops
    the copies drifting). ``fact_nodes``/``dim_nodes`` take a node count
    (placed on ``0..n-1``) or an explicit node iterable; the dim table uses
    ``seed + 1``. Returns ``(fact, dim, reference_sums)``.

    ``zipf=s`` draws fact keys from a Zipf(s) law over the keyspace (key
    ``r`` carries mass ``(r+1)^-s``); ``heavy_hitters=H`` routes ~half the
    rows to ``H`` seeded hot keys on top of whatever base law is active.
    Both are seeded and leave the default (``zipf=0, heavy_hitters=0``)
    fact table byte-identical to the uniform workload.
    """
    ks = keyspace if keyspace is not None else 2 * max(rows, dim_rows)
    if zipf or heavy_hitters:
        fact = _synth_skewed_fact(rows, ks, seed, zipf, heavy_hitters)
    else:
        fact = synth_table("f", rows, ks, seed=seed)
    dimc = synth_table("d", dim_rows, ks, seed=seed + 1, unique_keys=True)
    dim = Table({**dimc.columns,
                 "cat": jnp.arange(dim_rows, dtype=jnp.int32) % num_groups})
    ref = reference_query_numpy(fact, dim, num_groups=num_groups)
    fact_nodes = range(fact_nodes) if isinstance(fact_nodes, int) \
        else fact_nodes
    dim_nodes = range(dim_nodes) if isinstance(dim_nodes, int) else dim_nodes
    return (distribute(fact, fact_nodes, "A"),
            distribute(dim, dim_nodes, "B"), ref)


def zipf_weights(key_space: int, s: float) -> np.ndarray:
    """Normalized Zipf(s) mass over keys ``0..key_space-1`` (key ``r`` gets
    mass ``(r+1)^-s``). Shared by the generator and the tests that check
    the realized histogram against the requested law."""
    w = np.arange(1, int(key_space) + 1, dtype=np.float64) ** -float(s)
    return w / w.sum()


def _synth_skewed_fact(rows: int, key_space: int, seed: int,
                       zipf: float, heavy_hitters: int) -> Table:
    """Skewed twin of ``synth_table('f', ...)`` — same column recipe
    (int32 ``key``, float32 ``v0``/``v1``), different key law."""
    rng = np.random.default_rng(seed)
    if zipf:
        keys = rng.choice(int(key_space), size=rows,
                          p=zipf_weights(key_space, zipf))
    else:
        keys = rng.integers(0, key_space, size=rows)
    if heavy_hitters:
        h = int(heavy_hitters)
        hot = rng.permutation(int(key_space))[:h]
        mask = rng.random(rows) < 0.5
        keys = np.where(mask, hot[rng.integers(0, h, size=rows)], keys)
    cols = {"key": jnp.asarray(keys, jnp.int32)}
    for i in range(2):
        cols[f"v{i}"] = jnp.asarray(
            rng.standard_normal(rows, dtype=np.float32))
    return Table(cols)


@dataclass
class QueryStrategy:
    """S-M = static merge, S-H = static hash, DYN = decision workflow.

    "dynamic" is the refined cost-model decision node (paper Fig. 5 step 4);
    "dynamic_fig6" is the literal T1/T2 threshold node of Fig. 6. The
    strategy supplies the join node's decision function; everything else
    (late binding, per-phase nodes, materialization) is shared.
    """

    name: str   # static_merge | static_hash | dynamic | dynamic_fig6

    def join_method(self, ctx: DecisionContext) -> Decision:
        if self.name == "dynamic":
            from repro.analytics.decisions import cost_model_join_node
            return cost_model_join_node().decide(ctx)
        if self.name == "dynamic_fig6":
            from repro.analytics.decisions import join_decision
            return join_decision(ctx)
        func = "merge_join" if self.name == "static_merge" else "hash_join"
        dist_a, dist_b = ctx.data_dist["A"], ctx.data_dist["B"]
        nodes = tuple(sorted(dist_a.loc | dist_b.loc))
        scale = max(1, int((dist_a.size + dist_b.size) / ALPHA))
        return Decision(func, scale, Schedule("round-robin", nodes))


def resolve_join_decision(strategy: QueryStrategy, ctx: DecisionContext,
                          consolidate_threshold: int = 2 << 30,
                          ) -> tuple[Decision, bool]:
    """Compatibility shim: run the strategy's join choice once, up front.

    New code should build a workflow (``build_query_workflow``) so the join
    decision late-binds on observed scan output; this path exists for
    callers that make a single a-priori decision.
    """
    from repro.analytics.planner import consolidation_applies

    decision = strategy.join_method(ctx)
    total_bytes = sum(d.size for d in ctx.data_dist.values())
    return decision, consolidation_applies(
        strategy.name, decision, total_bytes, consolidate_threshold)


def plan_query_tasks(sim: ClusterSim, pc: PrivateController,
                     fact: DistTable, dim: DistTable,
                     strategy: QueryStrategy, app: str = "query",
                     consolidate_threshold: int | None = None,
                     workflow: DecisionWorkflow | None = None) -> None:
    """Emit the task DAG for the sub-query — thin wrapper over the
    workflow-driven planner (``plan_query_with_workflow``)."""
    plan_query_with_workflow(
        sim, pc, fact, dim, strategy, app=app, workflow=workflow,
        consolidate_threshold=consolidate_threshold)


# -- runtime execution: decisions -> real partitioned invocations ----------------


def plan_runtime_stages(app: str, fact_layout: Sequence[tuple[int, int]],
                        dim_layout: Sequence[tuple[int, int]],
                        decision: Decision, dist_f: DataDist,
                        consolidated: bool = False, num_groups: int = 64,
                        priority: int = 0) -> list:
    """Compatibility shim: materialize a single up-front join decision into
    the full physical stage list (scans + exchange + join + aggregation).
    The adaptive path builds the same stages incrementally via
    ``AdaptiveQueryPlan``."""
    return scan_stages(app, fact_layout, dim_layout, priority) + tail_stages(
        app, fact_layout, dim_layout, decision, dist_f,
        consolidated=consolidated, num_groups=num_groups, priority=priority)


def split_partitions(partitions, split: int) -> list:
    """Split each home node's partition into ``split`` row-range slices —
    the fine-grained ``[(node, table), ...]`` layout where a node hosts
    several map partitions (so the invoker's batch coalescing has same-node
    siblings to merge). Slices are ``TableSlice`` views: no copies until a
    scan reads them. The per-node byte totals — everything the decision
    nodes consume — are unchanged."""
    out = []
    for node, t in sorted(partitions.items()):
        k = max(1, min(int(split), t.num_rows or 1))
        bounds = np.linspace(0, t.num_rows, k + 1).astype(int)
        out.extend((node, t.slice(lo, hi))
                   for lo, hi in zip(bounds[:-1], bounds[1:]))
    return out


def prepare_query_plan(runtime, fact: DistTable, dim: DistTable,
                       strategy: QueryStrategy, app: str = "query",
                       priority: int = 10, num_groups: int = 64,
                       pc: PrivateController | None = None,
                       consolidate_threshold: int | None = None,
                       workflow: DecisionWorkflow | None = None,
                       map_split: int = 1, seed_tier: str | None = None,
                       reuse_inputs: bool = False,
                       ) -> tuple[AdaptiveQueryPlan, PrivateController]:
    """Planner entry point for a *named* application on a shared runtime.

    Observes the input distributions, opens the query's own late-bound
    ``WorkflowRun``, seeds the inputs into the shared store under ``app``'s
    namespace, and returns the ``AdaptiveQueryPlan`` (plus the private
    controller) ready for ``runtime.execute``. Several apps prepared against
    one runtime can then be driven concurrently — this is what
    ``repro.runtime.scheduler.QueryScheduler`` admits per query.

    ``map_split`` seeds each node's input as that many sub-partitions
    (``split_partitions``): map stages then run ``map_split`` invocations
    per node, which the invoker's batching coalesces back into one claim
    per node — the vectorized-data-plane benchmark knob.

    ``seed_tier`` ingests the inputs into a cold storage backend (e.g.
    ``"object"``) instead of memory — the Lambada cold-data scenario:
    first-touch scans read (and promote) through the emulated object
    store. ``reuse_inputs=True`` skips seeding when the store already
    holds the input stages (a warm re-query on the same runtime reads
    whatever tier the previous run left them in).
    """
    if pc is None:
        pc = PrivateController(app, runtime.gc, priority=priority)

    dist_f, dist_d = fact.data_dist(), dim.data_dist()
    pc.observe_data(dist_f)
    pc.observe_data(dist_d)
    wf = _resolve_workflow(workflow, strategy, consolidate_threshold)
    ctx = DecisionContext(
        data_dist={"A": dist_f, "B": dist_d},
        node_status=runtime.gc.node_status(), profile=dict(pc.profile))
    run = wf.start(ctx)
    run.app = app

    fact_parts = fact.partitions if map_split <= 1 \
        else split_partitions(fact.partitions, map_split)
    dim_parts = dim.partitions if map_split <= 1 \
        else split_partitions(dim.partitions, map_split)
    if reuse_inputs and runtime.store.stage_layout(app, "input/fact"):
        fact_layout = runtime.store.stage_layout(app, "input/fact")
        dim_layout = runtime.store.stage_layout(app, "input/dim")
    else:
        fact_layout = runtime.seed(app, "input/fact", fact_parts,
                                   tier=seed_tier)
        dim_layout = runtime.seed(app, "input/dim", dim_parts,
                                  tier=seed_tier)
    plan = AdaptiveQueryPlan(run, app, fact_layout, dim_layout,
                             num_groups=num_groups, priority=pc.priority)
    return plan, pc


def execute_query_runtime(fact: DistTable, dim: DistTable,
                          strategy: QueryStrategy, runtime=None,
                          gc: GlobalController | None = None,
                          pc: PrivateController | None = None,
                          app: str = "query", priority: int = 10,
                          num_groups: int = 64, invoker: str = "inline",
                          consolidate_threshold: int | None = None,
                          workflow: DecisionWorkflow | None = None,
                          barrier: bool = False, recovery="lineage",
                          max_recoveries: int = 8, batching: bool = True,
                          map_split: int = 1, pipeline: bool = False,
                          seed_tier: str | None = None,
                          reuse_inputs: bool = False):
    """Run the TPC-DS-like sub-query end-to-end on the serverless runtime.

    One decision workflow drives the whole query: the scan decision binds
    up front, the executor launches the (independent) scan stages, and when
    they complete the planner folds the observed post-filter distribution
    plus stage metrics back into the context and binds the join/exchange/
    aggregate decisions — the paper's interleaved decide→execute→re-decide
    loop. Pass ``workflow`` to share one workflow object across planners
    (e.g. with the simulator) and ``barrier=True`` to force the legacy
    stage-at-a-time executor. ``recovery``/``max_recoveries`` pick the
    failure-handling policy for lost shuffle stages (see ``DAGExecutor``).
    ``batching`` (only consulted when the runtime is built here) toggles
    the invoker's coalescing of batchable map invocations — the control
    plane sees identical decisions and metrics either way (tested).
    ``pipeline=True`` lets the executor honor the workflow's bound
    ``pipeline`` decision (partition-granularity launch + prefetch + fused
    probe); off, the same decision is still bound and audited but the
    stage barrier runs — decisions, record counts and results are
    identical either way (tested). Returns ``(group_sums, runtime)``.
    """
    from repro.runtime.executor import Runtime

    if runtime is None:
        if gc is None:
            nodes = sorted(set(fact.partitions) | set(dim.partitions))
            gc = GlobalController({n: 8 for n in nodes})
        runtime = Runtime(gc, invoker=invoker, batching=batching)
    plan, pc = prepare_query_plan(
        runtime, fact, dim, strategy, app=app, priority=priority,
        num_groups=num_groups, pc=pc,
        consolidate_threshold=consolidate_threshold, workflow=workflow,
        map_split=map_split, seed_tier=seed_tier, reuse_inputs=reuse_inputs)
    runtime.execute(None, pc=pc, planner=plan,
                    barrier=barrier, recovery=recovery,
                    max_recoveries=max_recoveries, pipeline=pipeline)
    return runtime.result(app), runtime


# -- real-data-plane execution (correctness path) --------------------------------


def execute_query_jax(fact: Table, dim: Table, method: str = "hash",
                      num_groups: int = 64) -> jnp.ndarray:
    """Run the logical query on the JAX data plane; returns per-group sums."""
    keep = fact["v0"] > 0
    filtered = ops.filter_table(fact, keep)
    joined = ops.join(filtered, dim, method=method)
    weights = jnp.where(joined["found"] & (joined["valid"] != 0),
                        joined["v0"] * joined["v1"], 0.0)
    group = joined["cat"].astype(jnp.int32) % num_groups
    return ops.groupby_sum(group, weights, num_groups)


# How far a run's group sums may sit from the float64 oracle, relative to
# the largest oracle group sum. The device accumulates float32 (one
# segment-sum per join partition), whose rounding grows with the terms per
# group; at 2^25 fact rows a group folds ~2^17 terms, and 1e-3 of the
# largest group leaves about two orders of magnitude of headroom over that
# rounding, while a dropped or doubled join partition moves the group sums
# by a sizeable share of their scale and still fails by far.
ORACLE_RTOL = 1e-3


def oracle_relative_error(got, ref) -> float:
    """``max |got - ref| / max |ref|`` — the norm-wise relative error the
    query runs are held to (``ORACLE_RTOL``)."""
    ref = np.asarray(ref, np.float64)
    err = np.abs(np.asarray(got, np.float64) - ref).max()
    return float(err / max(np.abs(ref).max(), np.finfo(np.float64).tiny))


def reference_query_numpy(fact: Table, dim: Table,
                          num_groups: int = 64) -> np.ndarray:
    """Pure-numpy oracle, independent of the code under test: float64
    per-group ``SUM(v0 * v1)`` over fact rows with ``v0 > 0`` whose key
    has a dim row (unmatched keys drop out; a duplicated dim key resolves
    to its last row, as a dict lookup would). One ``searchsorted`` over the
    sorted dim keys and one weighted ``bincount``, so it checks tens of
    millions of rows in seconds."""
    fk = np.asarray(fact["key"])
    v0 = np.asarray(fact["v0"]).astype(np.float64)
    v1 = np.asarray(fact["v1"]).astype(np.float64)
    dk = np.asarray(dim["key"])
    cat = np.asarray(dim["cat"])
    if dk.size == 0:
        return np.zeros(num_groups)
    order = np.argsort(dk, kind="stable")
    sorted_keys = dk[order]
    pos = np.maximum(np.searchsorted(sorted_keys, fk, side="right") - 1, 0)
    hit = (v0 > 0) & (sorted_keys[pos] == fk)
    groups = cat[order][pos][hit].astype(np.int64) % num_groups
    return np.bincount(groups, weights=(v0 * v1)[hit],
                       minlength=num_groups)
