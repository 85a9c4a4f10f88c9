"""The partitioned analytics function library.

Each entry is a stateless serverless function: it reads its inputs from the
shuffle store, computes with ``repro.analytics.operators`` (which routes
through the kernel dispatch layer ``repro.kernels.ops``) and writes its
outputs back — no state survives the invocation, so the invoker may retry
it after preemption. Registered names are what the executor puts into
``Invocation.func``; the decision tuple's ``func`` field ("hash_join" /
"merge_join") selects between the two join variants exactly as in the
paper's Fig. 6.

Hot functions are **single-pass and loop-free**: ``shuffle_write`` computes
one grouping permutation on the device and publishes every bucket as a
``TableSlice`` view over the permuted buffer through ``ctx.put_many`` (one
store round trip for all buckets); multi-partition reads concatenate with
one multi-way ``Table.concat_all`` per column; the final aggregate folds
all partials in one vectorized reduction. ``shuffle_write_loop`` keeps the
legacy per-bucket ``nonzero``/``take``/``put`` loop as the benchmark
baseline (``benchmarks/bench_dataplane.py``).

Stage-name and partition parameters arrive via ``ctx.params``:

    scan_filter      src, dst, partition [, filter_col, filter_gt]
    shuffle_write    src, dst, partition, num_buckets
    broadcast_write  src, dst, partition
    hash_join_partition / merge_join_partition
                     fact_stage, fact_partitions, dim_stage,
                     dim_partitions | "all", dst, partition, num_groups
    salted_join_partition
                     join params + fact_writers (one writer shard of a
                     heavy bucket) [, drop_keys]
    hot_filter_write src, src_partitions, keys, dst
    hot_join_partition
                     join params + keep_keys (heavy-hitter probe split)
    partial_aggregate  src, dst, partition, num_groups
    final_aggregate    src, dst, num_groups
    cpu_spin         dst, partition [, iters]
"""

from __future__ import annotations

from typing import Callable, Dict

import jax.numpy as jnp
import numpy as np

from repro.analytics import operators as ops
from repro.analytics.table import Table
from repro.kernels import ops as kops
from repro.obs.tracer import get_tracer

FUNCTIONS: Dict[str, Callable] = {}


def register(name: str):
    def deco(fn):
        FUNCTIONS[name] = fn
        return fn
    return deco


@register("cpu_spin")
def cpu_spin(ctx) -> None:
    """GIL-bound compute stage for the worker-plane benchmarks: a pure
    Python accumulation loop that holds the interpreter lock for its whole
    duration, so thread-backed invokers serialize it while process-backed
    workers run it truly in parallel (``benchmarks/bench_elastic.py``).
    The result is deterministic in ``(partition, iters)``, so fan-out
    outputs stay verifiable across backends."""
    p = ctx.params
    iters = int(p.get("iters", 100_000))
    x = int(p["partition"]) + 1
    acc = 0
    for i in range(iters):
        acc = (acc + x * i) % 1_000_003
    ctx.put(p["dst"], p["partition"],
            Table({"acc": jnp.asarray([acc], jnp.int32)}))


def _empty_joined() -> Table:
    return Table({"group": jnp.zeros((0,), jnp.int32),
                  "weight": jnp.zeros((0,), jnp.float32)})


@register("scan_filter")
def scan_filter(ctx) -> None:
    """Partition scan: read a base partition, drop filtered rows, rewrite.

    Unlike the in-process JAX path (static shapes + validity column), the
    runtime genuinely compacts: dropped rows never hit the shuffle store.
    """
    p = ctx.params
    t = ctx.get(p["src"], p["partition"])
    if t is None:
        return
    col = p.get("filter_col")
    if col is not None and t.num_rows:
        t = t.mask(t[col] > p.get("filter_gt", 0.0))
    ctx.put(p["dst"], p["partition"], t)


@register("shuffle_write")
def shuffle_write(ctx) -> None:
    """Hash-partition one input partition into the join's bucket space —
    the single-pass columnar path.

    One kernel dispatch (``ops.grouping_indices``: Pallas histogram +
    scatter on TPU, jitted stable sort elsewhere, padded to a power-of-two
    shape class so heterogeneous partitions share compilations) yields the
    grouping permutation and every bucket's offset range; one gather per
    column permutes the partition; each non-empty bucket is then a
    zero-copy ``TableSlice`` of the permuted buffer, published together
    via ``ctx.put_many``. The store appends this writer's slices to
    whatever other map instances wrote for the same buckets — that append
    *is* the all-to-all shuffle.
    """
    p = ctx.params
    t = ctx.get(p["src"], p["partition"])
    if t is None or t.num_rows == 0:
        return
    nb = int(p["num_buckets"])
    pids = ops.partition_ids(t["key"], nb)
    order, offsets = ops.grouping_indices(pids, nb)
    # land the permuted buffer on the host ONCE (one transfer per column):
    # every bucket slice is then a zero-copy numpy view, and readers
    # concatenate views with a memcpy — device programs are reserved for
    # the kernels, not for per-(shape, range) slice/concat plumbing
    permuted = Table(kops.host_copy(t.take(order).columns, "shuffle_write"))
    bounds = kops.host_copy(offsets, "shuffle_offsets")
    # skew detection rides the grouping we already paid for: the offset
    # diffs ARE the per-bucket row histogram, and the heavy-hitter sketch
    # is one fixed-shape hash-slot histogram (Pallas on TPU) plus an exact
    # host count of the candidate slots. Lands on the invocation record via
    # ctx.stats -> profile_feedback, where the planner's skew node reads
    # the observed (not estimated) distribution.
    rows_hist = np.diff(bounds)
    row_nb = sum(int(np.prod(v.shape[1:])) * v.dtype.itemsize
                 for v in permuted.columns.values())
    ctx.stats["partition_rows"] = tuple(int(r) for r in rows_hist)
    ctx.stats["partition_bytes"] = tuple(int(r) * row_nb for r in rows_hist)
    ctx.stats["hot_keys"] = kops.heavy_hitter_sketch(t["key"])
    out = {r: permuted.slice(bounds[r], bounds[r + 1])
           for r in range(nb) if bounds[r + 1] > bounds[r]}
    ctx.put_many(p["dst"], out)


@register("shuffle_write_loop")
def shuffle_write_loop(ctx) -> None:
    """Legacy per-bucket shuffle: one host round trip (``np.nonzero``), one
    gather and one store ``put`` *per bucket*. Kept as the benchmark
    baseline the batched columnar path is measured against; not planned by
    default."""
    p = ctx.params
    t = ctx.get(p["src"], p["partition"])
    if t is None or t.num_rows == 0:
        return
    nb = int(p["num_buckets"])
    pids = kops.host_copy(ops.partition_ids(t["key"], nb), "shuffle_pids")
    for r in range(nb):
        idx = np.nonzero(pids == r)[0]
        if idx.size:
            ctx.put(p["dst"], r, t.take(kops.device_copy(idx)))


@register("broadcast_write")
def broadcast_write(ctx) -> None:
    """Publish a (small) build-side partition for broadcast consumption.

    Every join instance later reads *all* partitions of ``dst``; the store
    charges each remote read to this partition's home node, reproducing the
    sender-serialization broadcast cost of Fig. 4(c).
    """
    p = ctx.params
    t = ctx.get(p["src"], p["partition"])
    if t is not None:
        ctx.put(p["dst"], p["partition"], t)


PREFETCH_WINDOW = 2     # in-flight fetches per side (double buffering)


def _read_side(ctx, stage: str, parts, window: int = PREFETCH_WINDOW,
               writers=None):
    """Concatenate a join side's partitions in ONE multi-way concat per
    column (``Table.concat_all``) instead of the O(P²) pairwise chain.

    Under an active pipeline plan the reads are double-buffered: the first
    ``window`` partitions are prefetched up front and partition ``i+window``
    starts fetching before partition ``i`` is consumed — per-partition read
    *order* (and therefore the store's fault-hook match counts per stage)
    is exactly the barrier path's. A writer-restricted read (``writers``)
    skips the prefetch cache entirely: prefetched handles hold full
    partitions, not this invocation's shard.
    """
    if parts == "all":
        return ctx.get_all(stage)
    parts = list(parts)
    # a single-partition side has nothing to double-buffer: a prefetch
    # thread would only add a spawn + GIL handoff to a read we immediately
    # block on
    pipelined = ctx.plan in ("pipelined", "fused") and len(parts) > 1 \
        and writers is None
    if pipelined:
        for part in parts[:window]:
            ctx.prefetch(stage, part)
    got = []
    for i, part in enumerate(parts):
        if pipelined and i + window < len(parts):
            ctx.prefetch(stage, parts[i + window])
        t = ctx.get(stage, part, writers=writers)
        if t is not None and t.num_rows:
            got.append(t)
    return Table.concat_all(got) if got else None


def _mitigation_view(fact, p):
    """Apply the skew plan's fact-side restrictions before joining.

    ``row_lo``/``row_hi`` select one salted sub-range of a heavy bucket —
    the range indexes the deterministic writer-ordered concatenation a
    bucket read produces, so the planner's histogram-derived splits land on
    exactly the rows it counted. ``drop_keys`` removes the heavy-hitter
    keys a broadcast split routes elsewhere; ``keep_keys`` is the hot-probe
    side of the same split. Absent params leave the fact side untouched,
    so the unmitigated plan's execution is byte-identical to before."""
    if fact is None or fact.num_rows == 0:
        return fact
    lo = p.get("row_lo")
    if lo is not None:
        lo, hi = int(lo), min(int(p["row_hi"]), fact.num_rows)
        if hi <= lo:
            return None
        fact = fact.slice(lo, hi).materialize()
    drop = p.get("drop_keys")
    if drop:
        keep = ~np.isin(kops.host_copy(fact["key"], "skew_keys"), list(drop))
        fact = fact.mask(kops.device_copy(keep))
    keep_keys = p.get("keep_keys")
    if keep_keys:
        keep = np.isin(kops.host_copy(fact["key"], "skew_keys"),
                       list(keep_keys))
        fact = fact.mask(kops.device_copy(keep))
    return fact


def _join_partition(ctx, method: str) -> None:
    p = ctx.params
    plan = ctx.plan
    if plan in ("pipelined", "fused"):
        # start the (small) build side streaming in while the fact side is
        # read — the cross-side half of the double buffering. A one-bucket
        # build side (co-partitioned merge join) is read directly: there is
        # no second fetch to overlap it with.
        dim_parts = list(ctx.partitions(p["dim_stage"])
                         if p["dim_partitions"] == "all"
                         else p["dim_partitions"])
        if len(dim_parts) > 1:
            for part in dim_parts:
                ctx.prefetch(p["dim_stage"], part)
    fact = _read_side(ctx, p["fact_stage"], p["fact_partitions"],
                      writers=p.get("fact_writers"))
    dim = _read_side(ctx, p["dim_stage"], p["dim_partitions"])
    fact = _mitigation_view(fact, p)
    if fact is None or fact.num_rows == 0 or dim is None or dim.num_rows == 0:
        ctx.put(p["dst"], p["partition"], _empty_joined())
        return
    if plan == "fused":
        # one dispatch replaces join -> where(found) -> mod: same output
        # encoding (non-matching rows carry group 0 / weight 0). Publish as
        # device arrays like the unfused path does, so the aggregation
        # stage reads the same array kind under either plan.
        group, weight = kops.fused_probe_groups(
            fact["key"], fact["v0"], fact["v1"], dim["key"], dim["cat"],
            int(p["num_groups"]))
        ctx.put(p["dst"], p["partition"],
                Table(kops.device_copy({"group": group, "weight": weight})))
        return
    # the join's dispatches, timed as one kernel span: with the device's
    # queue full, a dispatch itself waits for earlier work to retire
    with get_tracer().span("kernel/join", "kernel", method=method,
                           rows=fact.num_rows, build_rows=dim.num_rows,
                           path="jit"):
        joined = ops.join(fact, dim, method=method)
        found = joined["found"]
        # a shuffled (host) fact side multiplies on the host, then goes up
        weight = jnp.where(found,
                           kops.device_copy(joined["v0"] * joined["v1"]), 0.0)
        group = joined["cat"].astype(jnp.int32) % int(p["num_groups"])
    ctx.put(p["dst"], p["partition"],
            Table({"group": group, "weight": weight}))


@register("hash_join_partition")
def hash_join_partition(ctx) -> None:
    """Broadcast hash join: build over the dim side, probe the fact side."""
    _join_partition(ctx, "hash")


@register("merge_join_partition")
def merge_join_partition(ctx) -> None:
    """Shuffled sort-merge join over one co-partitioned bucket."""
    _join_partition(ctx, "merge")


@register("salted_join_partition")
def salted_join_partition(ctx) -> None:
    """One writer shard of a heavy shuffled bucket: sort-merge join of the
    ``fact_writers`` slices of the bucket against the bucket's dim side
    (replicated across the bucket's sub-joins), writing straight into an
    extra ``joined`` partition the aggregation folds like any other — no
    single invocation ever reads (or joins) the whole heavy bucket."""
    _join_partition(ctx, "merge")


@register("hot_filter_write")
def hot_filter_write(ctx) -> None:
    """Broadcast split, build side: collect the heavy-hitter keys' dim rows
    from the scan output and publish them as one replicated build partition
    for the hot probes. Writes nothing when no dim row matches (the hot
    joins then emit empty output — same result as an unmatched probe)."""
    p = ctx.params
    keys = [int(k) for k in p["keys"]]
    got = []
    for part in p["src_partitions"]:
        t = ctx.get(p["src"], part)
        if t is None or t.num_rows == 0:
            continue
        keep = np.isin(kops.host_copy(t["key"], "hot_keys"), keys)
        if keep.any():
            got.append(t.mask(kops.device_copy(keep)))
    if got:
        ctx.put(p["dst"], 0, Table.concat_all(got))


@register("hot_join_partition")
def hot_join_partition(ctx) -> None:
    """Broadcast split, probe side: hash-join one fact scan partition's
    heavy-hitter rows (``keep_keys``) against the replicated hot build
    side — per-writer parallelism replacing the one straggler bucket."""
    _join_partition(ctx, "hash")


@register("partial_aggregate")
def partial_aggregate(ctx) -> None:
    """Per-partition grouped partial sums — one segment-sum dispatch."""
    p = ctx.params
    g = int(p["num_groups"])
    t = ctx.get(p["src"], p["partition"])
    if t is None or t.num_rows == 0:
        vec = jnp.zeros((g,), jnp.float32)
    else:
        vec = ops.groupby_sum(t["group"], t["weight"], g)
    ctx.put(p["dst"], p["partition"], Table({"sum": vec}))


@register("final_aggregate")
def final_aggregate(ctx) -> None:
    """Fold every partial vector in one pass (float64 accumulation for a
    deterministic, order-independent total)."""
    p = ctx.params
    g = int(p["num_groups"])
    vecs = [t["sum"] for t in (ctx.get(p["src"], part)
                               for part in ctx.partitions(p["src"]))
            if t is not None and t.num_rows]
    total = (np.stack([v.astype(np.float64)
                       for v in kops.host_copy(vecs, "final_partials")])
             .sum(axis=0) if vecs else np.zeros(g, dtype=np.float64))
    ctx.put(p["dst"], 0,
            Table({"sum": kops.device_copy(total.astype(np.float32))}))
