"""Analytics case study: operators vs numpy oracle, decision nodes,
simulator invariants, and paper-trend assertions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.analytics import (
    QueryStrategy,
    Table,
    execute_query_jax,
    make_cluster,
    plan_query_tasks,
    reference_query_numpy,
    synth_table,
)
from repro.analytics import operators as ops
from repro.analytics.decisions import (
    T1,
    T2,
    cost_model_join_decision,
    join_decision,
    scheduling_decision,
)
from repro.analytics.simulator import SimTask
from repro.analytics.table import distribute, phantom
from repro.core.controllers import GlobalController, PrivateController
from repro.core.decisions import DataDist, DecisionContext
from repro.kernels import ops as kops


def make_tables(rows=2048, keyspace=1024, dim_rows=256, seed=0):
    fact = synth_table("f", rows, keyspace, seed=seed)
    dimc = synth_table("d", dim_rows, keyspace, seed=seed + 1,
                       unique_keys=True)
    dim = Table({**dimc.columns,
                 "cat": jnp.arange(dim_rows, dtype=jnp.int32) % 64})
    return fact, dim


# -- operator correctness -------------------------------------------------------


@pytest.mark.parametrize("method", ["hash", "merge"])
def test_join_methods_agree_with_oracle(method):
    fact, dim = make_tables()
    got = np.asarray(execute_query_jax(fact, dim, method=method))
    ref = reference_query_numpy(fact, dim)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)


def _reference_query_row_loop(fact, dim, num_groups=64):
    """The oracle as one Python step per fact row: the reference the
    vectorized ``reference_query_numpy`` must reproduce exactly."""
    lookup = {int(k): int(c) for k, c in zip(np.asarray(dim["key"]),
                                             np.asarray(dim["cat"]))}
    out = np.zeros(num_groups)
    for k, a, b in zip(np.asarray(fact["key"]),
                       np.asarray(fact["v0"]).astype(np.float64),
                       np.asarray(fact["v1"]).astype(np.float64)):
        if a > 0 and int(k) in lookup:
            out[lookup[int(k)] % num_groups] += a * b
    return out


@pytest.mark.parametrize("case", ["uniform", "duplicate_dim_keys",
                                  "empty_dim", "no_match"])
def test_reference_query_numpy_matches_row_loop(case):
    fact, dim = make_tables(rows=3000, keyspace=512, dim_rows=200, seed=3)
    if case == "duplicate_dim_keys":
        dim = Table({"key": jnp.asarray(np.arange(200) % 50, jnp.int32),
                     "cat": jnp.arange(200, dtype=jnp.int32)})
    elif case == "empty_dim":
        dim = Table({"key": jnp.zeros((0,), jnp.int32),
                     "cat": jnp.zeros((0,), jnp.int32)})
    elif case == "no_match":
        dim = Table({"key": dim["key"] + 10_000, "cat": dim["cat"]})
    got = reference_query_numpy(fact, dim, num_groups=16)
    np.testing.assert_array_equal(
        got, _reference_query_row_loop(fact, dim, num_groups=16))


def test_joins_agree_with_each_other():
    fact, dim = make_tables(seed=7)
    a = np.asarray(execute_query_jax(fact, dim, method="hash"))
    b = np.asarray(execute_query_jax(fact, dim, method="merge"))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), rows=st.sampled_from([256, 1024]),
       dim_rows=st.sampled_from([32, 128]))
def test_hash_join_property(seed, rows, dim_rows):
    """Property: every probe row matching a build key is found with the
    right index; non-matching rows are not found."""
    rng = np.random.default_rng(seed)
    build = jnp.asarray(rng.permutation(10 * dim_rows)[:dim_rows],
                        jnp.int32)
    probe = jnp.asarray(rng.integers(0, 10 * dim_rows, rows), jnp.int32)
    table = ops.build_hash_table(build)
    idx, found = ops.hash_join_indices(probe, build, table)
    build_np, probe_np = np.asarray(build), np.asarray(probe)
    lookup = {int(k): i for i, k in enumerate(build_np)}
    for j in range(rows):
        if int(probe_np[j]) in lookup:
            assert bool(found[j]), j
            assert int(idx[j]) == lookup[int(probe_np[j])]
        else:
            assert not bool(found[j])


def _home_slots(keys, cap):
    """The build's and the probe's first slot: the multiplicative hash's
    top ``log2(cap)`` bits, in numpy."""
    h = (np.asarray(keys, np.uint64) * int(ops.HASH_MULT)) % 2 ** 32
    return (h >> (32 - int(np.log2(cap)))).astype(np.int64)


def _colliding_keys(n, cap, home=3):
    """``n`` distinct keys whose first slot in a table of ``cap`` slots is
    ``home``: each round of the build places one of them."""
    ks = np.arange(200 * cap)
    return ks[_home_slots(ks, cap) == home][:n].astype(np.int32)


def _full_depth(table):
    """The same slots probed every one of the build's ``MAX_PROBES``
    rounds: the probe as deep as the budget allows."""
    return ops.HashTable(table.slots, jnp.int32(ops.MAX_PROBES))


@pytest.mark.parametrize("n", [1, 7, 100, 1000, 18_000, 102_000])
def test_dense_keys_are_placed_in_one_round(n):
    """Dense surrogate keys ``0..n-1`` at load <= 0.25 all land in their
    first slot, so the build records a probe depth of one round."""
    build = jnp.arange(n, dtype=jnp.int32)
    table = ops.build_hash_table(build)
    assert int(table.rounds) == 1
    probe = jnp.asarray(np.random.default_rng(n).integers(-5, n + 5, 4096),
                        jnp.int32)
    idx, found = ops.hash_join_indices(probe, build, table)
    p = np.asarray(probe)
    np.testing.assert_array_equal(np.asarray(found), (p >= 0) & (p < n))
    np.testing.assert_array_equal(np.asarray(idx), np.where(
        (p >= 0) & (p < n), p, 0))


@pytest.mark.parametrize("n_collide", [2, 5, 9])
def test_colliding_keys_probe_as_deep_as_placed(n_collide):
    """Keys that share a first slot are placed one per round; the probe
    runs exactly that deep and equals the full-depth probe, on probes
    that hit at every displacement and on probes that miss."""
    cap = kops._hash_table_size(n_collide + 8)
    hot = _colliding_keys(n_collide, cap)
    rng = np.random.default_rng(n_collide)
    others = np.setdiff1d(rng.permutation(50 * cap)[:64], hot)
    others = others[_home_slots(others, cap) != 3][:8].astype(np.int32)
    build_np = np.concatenate([hot, others])
    assert kops._hash_table_size(build_np.size) == cap
    build = jnp.asarray(build_np)
    table = ops.build_hash_table(build)
    rounds = int(table.rounds)
    assert rounds >= n_collide > 1
    # a key placed in round p sits p slots past its first slot
    slots = np.asarray(table.slots)
    where = {int(r): s for s, r in enumerate(slots) if r >= 0}
    assert sorted(where) == list(range(build_np.size))
    depth = [(where[i] - _home_slots(build_np[i:i + 1], cap)[0]) % cap
             for i in range(build_np.size)]
    assert max(depth) + 1 == rounds
    assert sorted(depth[:n_collide]) == list(range(n_collide))
    # every displacement hits; misses walk the hot cluster and beyond
    miss_hot = _colliding_keys(n_collide + 4, cap)[n_collide:]
    misses = np.setdiff1d(np.arange(10 * cap), build_np)[::7][:40]
    probe_np = np.concatenate([build_np, miss_hot, misses,
                               build_np[::-1]]).astype(np.int32)
    probe = jnp.asarray(probe_np)
    idx, found = ops.hash_join_indices(probe, build, table)
    ref_idx, ref_found = ops.hash_join_indices(probe, build,
                                               _full_depth(table))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_array_equal(np.asarray(found), np.asarray(ref_found))
    lookup = {int(k): i for i, k in enumerate(build_np)}
    np.testing.assert_array_equal(
        np.asarray(found), [int(k) in lookup for k in probe_np])
    np.testing.assert_array_equal(
        np.asarray(idx), [lookup.get(int(k), 0) for k in probe_np])


def test_too_small_a_budget_leaves_keys_out_as_before():
    """A build whose round budget cannot place every key records the whole
    budget as its depth; the keys it placed are found, the rest are not,
    as a full-depth probe of the same table finds them."""
    n = 8
    cap = kops._hash_table_size(n)
    build_np = _colliding_keys(n, cap)
    build = jnp.asarray(build_np)
    table = ops.build_hash_table(build, max_probes=4)
    assert int(table.rounds) == 4
    # the largest row index wins each round: rows 7, 6, 5, 4 are placed
    assert sorted(int(r) for r in np.asarray(table.slots) if r >= 0) == \
        [4, 5, 6, 7]
    probe_np = np.concatenate([build_np, build_np[::-1] + 1])
    probe = jnp.asarray(probe_np)
    idx, found = ops.hash_join_indices(probe, build, table)
    ref_idx, ref_found = ops.hash_join_indices(probe, build,
                                               _full_depth(table))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_array_equal(np.asarray(found), np.asarray(ref_found))
    placed = {int(k): i for i, k in enumerate(build_np) if i >= 4}
    np.testing.assert_array_equal(
        np.asarray(found), [int(k) in placed for k in probe_np])
    np.testing.assert_array_equal(
        np.asarray(idx), [placed.get(int(k), 0) for k in probe_np])


def test_probe_depth_is_traced_not_compiled():
    """Tables of one size and different depths share one compiled probe."""
    cap = kops._hash_table_size(16)
    dense = jnp.arange(16, dtype=jnp.int32)
    skewed = jnp.asarray(_colliding_keys(16, cap))
    probe = jnp.arange(64, dtype=jnp.int32)
    shallow = ops.build_hash_table(dense)
    deep = ops.build_hash_table(skewed)
    assert int(shallow.rounds) == 1 and int(deep.rounds) == 16
    ops.hash_join_indices(probe, dense, shallow)
    before = ops.hash_join_indices._cache_size()
    ops.hash_join_indices(probe, skewed, deep)
    assert ops.hash_join_indices._cache_size() == before


def test_partition_permutation_property():
    keys = jax.random.randint(jax.random.PRNGKey(0), (4096,), 0, 10_000,
                              jnp.int32)
    order, counts, pids = ops.partition_permutation(keys, 16)
    assert int(jnp.sum(counts)) == 4096
    sorted_pids = np.asarray(pids)[np.asarray(order)]
    assert (np.diff(sorted_pids) >= 0).all()     # grouped
    assert sorted(np.asarray(order).tolist()) == list(range(4096))


def test_groupby_sum_matches_numpy():
    gids = jax.random.randint(jax.random.PRNGKey(1), (512,), 0, 8, jnp.int32)
    vals = jax.random.normal(jax.random.PRNGKey(2), (512,))
    got = np.asarray(ops.groupby_sum(gids, vals, 8))
    ref = np.zeros(8)
    np.add.at(ref, np.asarray(gids), np.asarray(vals))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# -- decision nodes (paper Fig. 6) ----------------------------------------------


def _ctx(size_a, size_b, nodes_a, nodes_b, cluster=12, slots=8):
    gc = GlobalController({n: slots for n in range(cluster)})
    return DecisionContext(
        data_dist={
            "A": DataDist("A", {n: size_a // len(nodes_a) for n in nodes_a}),
            "B": DataDist("B", {n: size_b // len(nodes_b) for n in nodes_b}),
        },
        node_status=gc.node_status())


def test_fig6_small_dim_table_picks_hash():
    ctx = _ctx(400 << 20, 10 << 20, range(12), range(2))
    d = join_decision(ctx)
    assert d.func == "hash_join"
    assert d.schedule.policy == "packing"


def test_fig6_comparable_tables_large_cluster_picks_merge():
    ctx = _ctx(400 << 20, 100 << 20, range(12), range(2))
    assert (400 / 100) < T1 and 12 > T2
    d = join_decision(ctx)
    assert d.func == "merge_join"
    assert d.schedule.policy == "round-robin"


def test_cost_model_broadcast_grows_with_cluster():
    """Fig. 4(c): hash join estimate grows with cluster size; merge's
    doesn't — so the decision flips on large clusters. Hermetic: fixed
    operator rates injected through the profiling feedback channel."""
    rates = {"merge_join": 60e6, "hash_build": 500e6, "hash_probe": 300e6,
             "scan": 2e9, "sort": 120e6, "agg": 2e9}
    ctx_small = _ctx(400 << 20, 80 << 20, range(4), range(2), cluster=4)
    ctx_small.profile = {"rates": rates}
    ctx_large = _ctx(400 << 20, 80 << 20, range(20), range(2), cluster=20)
    ctx_large.profile = {"rates": rates}
    small = cost_model_join_decision(ctx_small)
    large = cost_model_join_decision(ctx_large)
    assert small.func == "hash_join"
    assert large.func == "merge_join"


def test_scheduling_node_packs_under_skew():
    gc = GlobalController({n: 8 for n in range(8)})
    uniform = DecisionContext(
        data_dist={"A": DataDist("A", {n: 100 for n in range(8)},
                                 skew=1.0)},
        node_status=gc.node_status())
    skewed = DecisionContext(
        data_dist={"A": DataDist("A", {0: 700, 1: 50, 2: 50}, skew=4.0)},
        node_status=gc.node_status())
    assert scheduling_decision(uniform).schedule.policy == "round-robin"
    assert scheduling_decision(skewed).schedule.policy == "packing"


# -- simulator ----------------------------------------------------------------


def test_simulator_respects_dependencies_and_slots():
    gc, sim = make_cluster(2, slots=1)
    sim.submit(SimTask("a", "app", 1.0, node=0))
    sim.submit(SimTask("b", "app", 1.0, node=0, deps=("a",)))
    out = sim.run()
    assert sim.tasks["b"].started >= sim.tasks["a"].finished
    assert out["completion"]["app"] == pytest.approx(2.0, rel=1e-6)


def test_simulator_transfers_serialize_on_nic():
    gc, sim = make_cluster(3)
    # two transfers from the same source must serialize
    sim.submit(SimTask("x", "app", 0.0, node=1,
                       transfers={0: int(1.25e9)}))   # 1s at 1.25 GB/s
    sim.submit(SimTask("y", "app", 0.0, node=2,
                       transfers={0: int(1.25e9)}))
    out = sim.run()
    assert out["completion"]["app"] == pytest.approx(2.0, rel=0.01)


def test_simulator_allocation_rate_bounds():
    gc, sim = make_cluster(2, slots=2)
    for i in range(8):
        sim.submit(SimTask(f"t{i}", "app", 0.5))
    out = sim.run()
    rate = out["allocation"].allocation_rate()
    assert 0.0 < rate <= 1.0


def test_flexible_task_backfills_most_free_node():
    """Regression for the dead not-placed branch in _try_start: a flexible
    (node=None) task must land on the node with the most free slots."""
    gc, sim = make_cluster(2, slots=2)
    gc.commit("other", 5, [0])               # node 0: 1 free, node 1: 2 free
    placements = {}
    gc.subscribe(lambda ev, c: placements.setdefault(c.tag, c.placement)
                 if ev == "commit" else None)
    sim.submit(SimTask("flex", "app", 1.0))
    sim.run()
    assert placements["flex"] == (1,)


def test_background_tasks_backfill_idle_slots():
    """Fig. 8: low-priority tasks run in the gaps without delaying the
    high-priority app beyond its solo completion time."""
    def build(with_bg):
        gc, sim = make_cluster(2, slots=2)
        sim.submit(SimTask("hi/1", "query", 1.0, node=0, priority=10))
        sim.submit(SimTask("hi/2", "query", 1.0, node=0, priority=10,
                           deps=("hi/1",)))
        if with_bg:
            for i in range(6):
                sim.submit(SimTask(f"bg/{i}", "bg", 0.5, priority=0))
        return sim.run()

    solo = build(False)
    shared = build(True)
    assert shared["completion"]["query"] <= solo["completion"]["query"] + 1e-6
    assert shared["allocation"].allocation_rate() \
        > solo["allocation"].allocation_rate()


# -- end-to-end strategy comparison (paper Fig. 7 trend) -------------------------


def test_dynamic_strategy_never_worst():
    results = {}
    for strat in ("static_merge", "static_hash", "dynamic"):
        times = []
        for gb in (2, 6):
            gc, sim = make_cluster(6)
            pc = PrivateController("query", gc, priority=10)
            f = phantom("A", int(gb * 0.9 * 2 ** 30), range(6))
            d = phantom("B", int(gb * 0.05 * 2 ** 30), range(2))
            plan_query_tasks(sim, pc, f, d, QueryStrategy(strat))
            times.append(sim.run()["completion"]["query"])
        results[strat] = times
    for i in range(2):
        worst = max(r[i] for r in results.values())
        assert results["dynamic"][i] < worst * 1.001
