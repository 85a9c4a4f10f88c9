"""Skew-adaptive exchange: the eighth decision node end to end.

The tentpole contract under test: shuffle writers feed an observed
per-bucket histogram + heavy-hitter sketch into ``profile_feedback``, the
``skew`` node binds on it *between* exchange and join (none / salted /
broadcast), and the mitigation stages it materializes are data-plane
invisible — the oracle result is identical for every forced mitigation,
the runtime and the simulator bind identical eight-node sequences, and
seeded fault plans recover through the mitigated DAG exactly like the
plain one. The salted path's quantized sub-join chunks must not fan the
compile cache (shape-class regression), and the skewed workload generator
must actually realize the Zipf law it promises.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.analytics import (
    QueryStrategy,
    execute_query_runtime,
    synth_query_tables,
)
from repro.analytics.planner import (
    build_query_workflow,
    plan_query_with_workflow,
    shuffle_skew_feedback,
    tail_stages,
)
from repro.analytics.query import zipf_weights
from repro.analytics.simulator import ClusterSim
from repro.core.controllers import GlobalController, PrivateController
from repro.core.decisions import (
    DataDist,
    Decision,
    Schedule,
    merge_hot_keys,
    skew_mitigation,
)
from repro.kernels import ops as kops
from repro.runtime import FaultInjector, FaultPlan, Runtime
from tests._hypothesis_compat import given, settings, st

STRATEGIES = ("static_merge", "static_hash", "dynamic", "dynamic_fig6")
EIGHT_NODES = ["scan", "join", "exchange", "skew", "aggregate",
               "pipeline", "elastic", "tiering"]


class FanoutStrategy(QueryStrategy):
    """Pin the join fan-out: small test tables bind scale=1, which the
    skew guard (rightly) treats as unsplittable — mitigation tests need a
    real bucket space."""

    def __init__(self, name: str, fanout: int):
        super().__init__(name)
        self.fanout = fanout

    def join_method(self, ctx):
        d = super().join_method(ctx)
        return Decision(d.func, self.fanout, d.schedule, extras=d.extras)


@pytest.fixture(scope="module")
def skewed_tables():
    return synth_query_tables(rows=1 << 14, dim_rows=1024, zipf=1.5, seed=3)


@pytest.fixture(scope="module")
def small_skewed_tables():
    return synth_query_tables(rows=4096, dim_rows=512, zipf=1.5, seed=3)


def _run(tables, strat="static_merge", fanout=8, force=None, plan=None,
         invoker="inline", pipeline=False, **wf_kw):
    fd, dd, ref = tables
    gc = GlobalController({n: 8 for n in range(4)})
    rt = Runtime(gc, invoker=invoker)
    if plan is not None:
        FaultInjector(plan).install(rt)
    strategy = FanoutStrategy(strat, fanout)
    wf = build_query_workflow(strategy, skew_force=force, **wf_kw)
    got, _ = execute_query_runtime(fd, dd, strategy, runtime=rt,
                                   workflow=wf, pipeline=pipeline,
                                   recovery="lineage")
    np.testing.assert_allclose(got, ref, atol=1e-3)
    assert sum(gc.used.values()) == 0
    return rt, wf.last_run


# -- workload generator: the law it promises is the law it draws -------------------


def test_zipf_workload_matches_requested_law():
    s, rows = 1.2, 1 << 15
    fd, _, _ = synth_query_tables(rows=rows, dim_rows=256, zipf=s, seed=9)
    keys = np.concatenate([np.asarray(t["key"])
                           for _, t in sorted(fd.partitions.items())])
    assert keys.size == rows
    ks = 2 * max(rows, 256)
    emp = np.bincount(keys, minlength=ks) / rows
    th = zipf_weights(ks, s)
    # the head of the law is where the mass (and the skew) lives: every
    # top-20 key's realized frequency sits within sampling noise of its
    # theoretical mass
    for k in range(20):
        tol = 6 * np.sqrt(th[k] * (1 - th[k]) / rows) + 1e-4
        assert abs(emp[k] - th[k]) < tol, (k, emp[k], th[k])
    # and the head dominates like Zipf(1.2) says it should
    assert emp[:20].sum() > 0.5 * th[:20].sum()


def test_heavy_hitters_route_about_half_the_mass():
    fd, _, _ = synth_query_tables(rows=1 << 14, dim_rows=256,
                                  heavy_hitters=4, seed=5)
    keys = np.concatenate([np.asarray(t["key"])
                           for _, t in sorted(fd.partitions.items())])
    _, counts = np.unique(keys, return_counts=True)
    top4 = np.sort(counts)[-4:].sum() / keys.size
    assert 0.42 < top4 < 0.58


def test_default_workload_byte_identical_without_skew_params():
    base = synth_query_tables(2048, 256, seed=1)
    skew = synth_query_tables(2048, 256, seed=1, zipf=0.0, heavy_hitters=0)
    for (na, ta), (nb, tb) in zip(sorted(base[0].partitions.items()),
                                  sorted(skew[0].partitions.items())):
        assert na == nb
        for c in ta.columns:
            np.testing.assert_array_equal(np.asarray(ta[c]),
                                          np.asarray(tb[c]))
    np.testing.assert_array_equal(base[2], skew[2])


# -- the pure mitigation rule ------------------------------------------------------


def test_rule_guards_empty_and_single_bucket():
    assert skew_mitigation((), ()) == ("none", (), 0, ())
    for force in (None, "none", "salted", "broadcast"):
        assert skew_mitigation((10_000,), (), force=force)[0] == "none"


def test_rule_balanced_and_small_histograms_stay_none():
    assert skew_mitigation((10, 12, 11, 9), ())[0] == "none"     # < min_rows
    assert skew_mitigation((2000, 2100, 1900, 2000), ())[0] == "none"


def test_rule_lopsided_without_hot_key_salts():
    rows = (24_000, 2000, 2000, 2000, 2000, 2000, 2000, 2000)
    func, heavy, salt, hot = skew_mitigation(rows, ())
    assert func == "salted" and hot == ()
    assert heavy == ((0, 24_000),)
    # salt = ceil(max/mean) clamped to [2, salt_cap]
    mean = sum(rows) / len(rows)
    assert salt == min(8, max(2, int(np.ceil(24_000 / mean))))


def test_rule_dominating_key_broadcasts():
    rows = (24_000, 2000, 2000, 2000, 2000, 2000, 2000, 2000)
    sketch = ((7, 20_000), (3, 100))
    func, heavy, salt, hot = skew_mitigation(rows, sketch)
    assert func == "broadcast" and salt >= 2   # shards the heavy reads too
    assert hot == (7,)                    # 100 rows is below hot_frac
    assert heavy == ((0, 24_000),)


def test_rule_force_pins_each_mitigation():
    rows = (2000, 2100, 1900, 2000)       # balanced: auto would say none
    assert skew_mitigation(rows, ((5, 900),), force="none")[0] == "none"
    func, heavy, salt, _ = skew_mitigation(rows, (), force="salted")
    assert func == "salted" and salt >= 2
    assert heavy == ((1, 2100),)          # argmax bucket, split anyway
    func, _, _, hot = skew_mitigation(rows, ((5, 900),), force="broadcast")
    assert func == "broadcast" and hot == (5,)     # 900 clears hot_frac
    func, _, _, hot = skew_mitigation(rows, ((5, 400), (9, 300), (2, 10)),
                                      force="broadcast")
    assert func == "broadcast" and hot == (5, 9)   # top-2 sketch fallback
    assert skew_mitigation(rows, (), force="broadcast")[0] == "none"


def test_rule_never_salts_more_than_half_the_buckets():
    rng = np.random.default_rng(0)
    for _ in range(50):
        n = int(rng.integers(2, 33))
        rows = tuple(int(r) for r in rng.integers(0, 10_000, size=n))
        _, heavy, _, _ = skew_mitigation(rows, ())
        assert len(heavy) <= n // 2       # >= 2x mean fits at most n/2 times


# -- sketch + salting kernels ------------------------------------------------------


def test_heavy_hitter_sketch_exact_and_deterministic():
    rng = np.random.default_rng(4)
    keys = np.concatenate([np.full(5000, 7), np.full(3000, 42),
                           rng.integers(0, 1 << 14, size=2000)])
    rng.shuffle(keys)
    import jax.numpy as jnp

    sk = kops.heavy_hitter_sketch(jnp.asarray(keys, jnp.int32))
    assert sk == kops.heavy_hitter_sketch(jnp.asarray(keys, jnp.int32))
    assert sk[0] == (7, int((keys == 7).sum()))
    assert sk[1] == (42, int((keys == 42).sum()))
    assert kops.heavy_hitter_sketch(jnp.asarray([], jnp.int32)) == ()


def test_merge_hot_keys_sums_and_orders():
    merged = merge_hot_keys([((1, 10), (2, 5)), ((2, 9), (3, 14))])
    assert merged == ((2, 14), (3, 14), (1, 10))     # ties: smaller key
    assert merge_hot_keys([((k, 1),) for k in range(20)], k=4) == \
        ((0, 1), (1, 1), (2, 1), (3, 1))


def test_salted_ranges_cover_disjoint_pow2_chunks():
    for total, salt in ((3662, 4), (1000, 8), (17, 2), (4096, 4)):
        ranges = kops.salted_ranges(total, salt)
        assert ranges[0][0] == 0 and ranges[-1][1] == total
        for (_, hi), (lo2, _) in zip(ranges, ranges[1:]):
            assert hi == lo2
        widths = {hi - lo for lo, hi in ranges}
        assert len(widths) <= 2           # full pow2 chunk + one remainder
        full = max(widths)
        assert full & (full - 1) == 0     # power of two
    assert kops.salted_ranges(0, 4) == ()


# -- end to end: every mitigation is oracle-equal and audited ----------------------


@pytest.mark.parametrize("force,expect", [(None, "broadcast"),
                                          ("none", "none"),
                                          ("salted", "salted"),
                                          ("broadcast", "broadcast")])
def test_forced_mitigations_oracle_equal(skewed_tables, force, expect):
    rt, run = _run(skewed_tables, force=force)
    assert [n for n, _ in run.sequence] == EIGHT_NODES
    skew_d = run.decisions["skew"]
    assert skew_d.func == expect
    stage_names = {r.name.split("/")[1] for r in rt.metrics.records}
    if expect == "salted":
        assert "salted_join" in stage_names
        assert skew_d.extra("salt", 0) >= 2 and skew_d.extra("heavy", ())
    elif expect == "broadcast":
        # a broadcast split also writer-shards the hot buckets' reads
        assert {"hot_build", "hot_join", "salted_join"} <= stage_names
        assert skew_d.extra("hot_keys", ())
        assert skew_d.extra("salt", 0) >= 2
    else:
        assert not {"salted_join", "hot_build", "hot_join"} & stage_names


def test_auto_policy_uniform_stays_none():
    tables = synth_query_tables(rows=1 << 14, dim_rows=1024, seed=3)
    _, run = _run(tables)
    assert run.decisions["skew"].func == "none"
    assert run.decisions["skew"].extra("ratio", 0.0) < 2.0


def test_pipelined_executor_runs_mitigated_plans(skewed_tables):
    for force in ("salted", "broadcast"):
        _run(skewed_tables, force=force, pipeline=True, invoker="threads")


@pytest.mark.parametrize("force", ["salted", "broadcast"])
def test_mitigated_plans_on_process_backend(small_skewed_tables, force):
    """Writer-restricted sub-join reads must survive the worker RPC: the
    ``writers=`` subset travels inside the get message and the host
    services it against the per-writer blob map (regression: the new kwarg
    once broke every process-backend read)."""
    fd, dd, ref = small_skewed_tables
    gc = GlobalController({n: 8 for n in range(4)})
    rt = Runtime(gc, invoker="process", max_workers=2)
    try:
        strategy = FanoutStrategy("static_merge", 8)
        wf = build_query_workflow(strategy, skew_force=force)
        got, _ = execute_query_runtime(fd, dd, strategy, runtime=rt,
                                       workflow=wf, pipeline=True)
        np.testing.assert_allclose(got, ref, atol=1e-3)
        assert wf.last_run.decisions["skew"].func == force
        stage_names = {r.name.split("/")[1] for r in rt.metrics.records}
        assert "salted_join" in stage_names
    finally:
        rt.invoker.shutdown()


def test_observed_feedback_reaches_profile_and_tracer(skewed_tables):
    from repro.obs.tracer import Tracer, set_tracer

    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        _, run = _run(skewed_tables)
    finally:
        set_tracer(prev)
    rows = run.ctx.profile["skew.partition_rows"]
    nbytes = run.ctx.profile["skew.partition_bytes"]
    hot = run.ctx.profile["skew.hot_keys"]
    assert len(rows) == 8 and len(nbytes) == 8
    assert sum(rows) > 0 and hot and hot[0][1] >= hot[-1][1]
    # the partition balance the skew node decided on rides its span
    (skew,) = [s for s in tracer.spans("query") if s.name == "decide/skew"]
    assert skew.cat == "planner"
    assert skew.attrs["max_partition_bytes"] == max(nbytes)
    assert skew.attrs["mean_partition_bytes"] == int(sum(nbytes) / 8)
    assert skew.attrs["hot_keys"] == len(hot)
    assert skew.attrs["value"] == run.decisions["skew"].func


# -- cross-plane parity: the sim materializes the same skew decision ---------------


@pytest.mark.parametrize("force", [None, "salted"])
def test_skew_decision_parity_across_planes(small_skewed_tables, force):
    fd, dd, ref = small_skewed_tables
    strategy = FanoutStrategy("dynamic", 8)
    wf = build_query_workflow(strategy, skew_force=force)

    gc_rt = GlobalController({n: 8 for n in range(4)})
    rt = Runtime(gc_rt)
    got, _ = execute_query_runtime(fd, dd, strategy, runtime=rt,
                                   workflow=wf)
    np.testing.assert_allclose(got, ref, atol=1e-3)
    seq_rt = [(s, d.func, d.scale, d.extras) for s, d in
              wf.last_run.sequence]

    gc_sim = GlobalController({n: 8 for n in range(4)})
    sim = ClusterSim(gc_sim)
    pc = PrivateController("query", gc_sim, priority=10)
    plan_query_with_workflow(sim, pc, fd, dd, strategy, workflow=wf)
    sim.run()
    seq_sim = [(s, d.func, d.scale, d.extras) for s, d in
               wf.last_run.sequence]

    assert [s for s, *_ in seq_rt] == EIGHT_NODES
    assert seq_rt == seq_sim        # heavy buckets / salt / hot keys too


def test_sim_feedback_recomputes_runtime_histogram(skewed_tables):
    """The simulator's stand-in histogram is *exactly* the runtime's
    observed one — same kernels over the same partitions."""
    fd, dd, _ = skewed_tables
    rows, nbytes, hot = shuffle_skew_feedback(fd, 8)
    _, run = _run(skewed_tables)
    assert run.ctx.profile["skew.partition_rows"] == rows
    assert run.ctx.profile["skew.partition_bytes"] == nbytes
    assert run.ctx.profile["skew.hot_keys"] == hot


# -- mitigation stages carry sound needs edges -------------------------------------


def _mitigated_stages(skew):
    join_d = Decision("merge_join", 4, Schedule("round-robin", (0, 1)))
    return {s.name: s for s in tail_stages(
        "q", [(0, 0), (1, 1)], [(0, 0)], join_d,
        DataDist("A", {0: 1 << 20}),
        exchange=Decision("shuffle", 4, Schedule("round-robin", (0, 1))),
        skew=skew)}


def test_salted_stage_needs_edges():
    skew = Decision("salted", 4, Schedule("round-robin", (0, 1)),
                    extras=(("heavy", ((1, 9000),)), ("salt", 2),
                            ("hot_keys", ())))
    stages = _mitigated_stages(skew)
    fact_writers = {"q/shuffle_fact/0", "q/shuffle_fact/1"}
    # the heavy bucket is handed to the sub-joins; plain join skips it
    assert [iv.index for iv in stages["join"].invocations] == [0, 2, 3]
    subs = stages["salted_join"].invocations
    assert len(subs) == 2
    groups = []
    for iv in subs:
        group = set(iv.params["fact_writers"])
        groups.append(group)
        # per-shard needs: this shard's fact writers + the whole dim side
        assert set(iv.needs) == group | {"q/shuffle_dim/0"}
        assert iv.params["fact_partitions"] == [1]
        # shard outputs are extra joined partitions past the join fan-out
        assert iv.params["dst"] == "joined" and iv.params["partition"] >= 4
    # shards partition the writer set: disjoint, covering
    assert groups[0] & groups[1] == set()
    assert groups[0] | groups[1] == fact_writers
    # buckets now outlive the join stage: partial_agg reclaims them
    assert stages["join"].ephemeral_inputs == ()
    assert set(stages["partial_agg"].ephemeral_inputs) >= \
        {"joined", "fact_buckets", "dim_buckets"}
    agg = {iv.index: iv for iv in stages["partial_agg"].invocations}
    assert 1 not in agg            # no joined[1] exists to aggregate
    assert agg[0].needs == ("q/join/0",)
    assert agg[4].needs == ("q/salted_join/0",)
    assert agg[5].needs == ("q/salted_join/1",)


def test_broadcast_shards_hot_bucket_reads():
    skew = Decision("broadcast", 2, Schedule("round-robin", (0, 1)),
                    extras=(("heavy", ((1, 9000),)), ("salt", 2),
                            ("hot_keys", (3, 11))))
    stages = _mitigated_stages(skew)
    hot_buckets = {int(b) for b in np.asarray(
        kops.partition_ids(np.asarray((3, 11), np.int32), 4))}
    # the hot buckets leave the plain join for the writer-sharded sub-joins
    assert {iv.index for iv in stages["join"].invocations} == \
        set(range(4)) - hot_buckets
    subs = stages["salted_join"].invocations
    assert len(subs) == 2 * len(hot_buckets)
    for iv in subs:
        assert tuple(iv.params["drop_keys"]) == (3, 11)
        # shard ids start past the hot_join probes (n_join + n_fact)
        assert iv.params["dst"] == "joined" and iv.params["partition"] >= 6
    agg_parts = {iv.index for iv in stages["partial_agg"].invocations}
    assert agg_parts == (set(range(4)) - hot_buckets) | {4, 5} | \
        {6 + i for i in range(len(subs))}


def test_broadcast_stage_needs_edges():
    skew = Decision("broadcast", 2, Schedule("round-robin", (0, 1)),
                    extras=(("heavy", ()), ("salt", 0),
                            ("hot_keys", (3, 11))))
    stages = _mitigated_stages(skew)
    build, = stages["hot_build"].invocations
    assert set(build.needs) == {"q/scan_dim/0"}
    assert tuple(build.params["keys"]) == (3, 11)
    hot = {iv.index: iv for iv in stages["hot_join"].invocations}
    assert set(hot) == {0, 1}
    for i, iv in hot.items():
        assert set(iv.needs) == {f"q/scan_fact/{i}", "q/hot_build/0"}
        assert iv.params["partition"] == 4 + i   # appended after n_join
    # the buckets holding the hot keys drop them from the plain join
    hot_buckets = {int(b) for b in np.asarray(
        kops.partition_ids(np.asarray((3, 11), np.int32), 4))}
    for iv in stages["join"].invocations:
        assert ("drop_keys" in iv.params) == (iv.index in hot_buckets)
    agg_parts = {iv.index for iv in stages["partial_agg"].invocations}
    assert agg_parts == {0, 1, 2, 3, 4, 5}
    assert "dim_hot" in stages["partial_agg"].ephemeral_inputs


# -- compile-cache discipline under salting ----------------------------------------


def test_salted_run_does_not_fan_the_compile_cache(skewed_tables):
    _run(skewed_tables, force="salted")        # warm every shape once
    classes = kops.shape_class_count()
    cache = kops.grouping_cache_size()
    _run(skewed_tables, force="salted")
    assert kops.shape_class_count() == classes
    got = kops.grouping_cache_size()
    assert got == -1 or got == cache           # -1: jax internals moved


# -- invariance: mitigation survives seeded fault schedules ------------------------


_BASELINE: dict = {}


def _fault_view(rt, run):
    return {
        "sequence": [(n, d.func, d.scale) for n, d in run.sequence],
        "skew_extras": run.decisions["skew"].extras,
        # a set: recovery re-executes producers, so an invocation can
        # commit more than once — what must not change is *which* ones do
        "ok_invs": sorted(
            {r.name for r in rt.metrics.records if r.status == "ok"}),
    }


def _check_fault_invariance(small_skewed_tables, strat, force, seed):
    """For any strategy x forced mitigation, a seeded crash+loss schedule
    changes *nothing* the control plane audits: same eight decisions (skew
    extras included), same set of committed invocations (retries add
    records, not commits), same oracle-equal result."""
    key = (strat, force)
    if key not in _BASELINE:
        rt, run = _run(small_skewed_tables, strat=strat, force=force)
        _BASELINE[key] = _fault_view(rt, run)
    plan = FaultPlan.seeded(seed, stages=("shuffle_fact", "join"),
                            data_stages=("joined", "fact_buckets"),
                            delay=0.01)
    rt, run = _run(small_skewed_tables, strat=strat, force=force,
                   plan=plan, invoker="threads")
    assert _fault_view(rt, run) == _BASELINE[key]


@pytest.mark.parametrize("strat,force,seed", [
    ("static_merge", "salted", 7),
    ("dynamic", "broadcast", 7),
    ("static_hash", "none", 3),
])
def test_mitigation_invariant_under_pinned_faults(small_skewed_tables,
                                                  strat, force, seed):
    """Deterministic anchor of the property below — runs even where
    hypothesis is not installed."""
    _check_fault_invariance(small_skewed_tables, strat, force, seed)


@settings(deadline=None, max_examples=10)
@given(strat=st.sampled_from(STRATEGIES),
       force=st.sampled_from(("none", "salted", "broadcast")),
       seed=st.integers(0, 5))
def test_mitigation_invariant_under_seeded_faults(small_skewed_tables,
                                                  strat, force, seed):
    _check_fault_invariance(small_skewed_tables, strat, force, seed)
