"""The process-backed worker plane (``repro.runtime.workers``).

Covers the pool's cold-start economics (warm LIFO reuse, idle reap,
resize), oracle equivalence of a full query on the ``process`` backend,
SIGKILL chaos — killed workers never leak controller slots, never leave
partial store writes, and heal through the standard crash-retry/lineage
machinery — and the elastic decision node's behavior on both data planes
(the runtime pool and the simulator's cold-start twin).

Worker subprocesses use the "spawn" start method and pay a real jax import
per cold start (~1s locally), so pools here stay at 1-2 workers.
"""

import time

import numpy as np
import pytest

from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.analytics import QueryStrategy, execute_query_runtime
from repro.analytics.simulator import ClusterSim, SimTask
from repro.core.controllers import GlobalController
from repro.core.decisions import worker_pool_target
from repro.runtime import (
    FaultInjector,
    FaultPlan,
    QueryJob,
    QueryScheduler,
    Runtime,
    WorkerKillFault,
    WorkerPool,
)
from tests.test_runtime import make_dist_tables


# -- pool economics (no query machinery involved) ---------------------------------


def test_pool_warm_reuse_and_function_seconds():
    pool = WorkerPool(max_workers=1)
    try:
        w, cold = pool.lease()
        assert cold and w.pid is not None
        pid = w.pid
        pool.release(w, busy_s=0.5)
        w2, cold2 = pool.lease()
        # LIFO warm reuse: same process, no second provision
        assert not cold2 and w2.pid == pid
        pool.release(w2, busy_s=0.25)
        assert pool.cold_starts == 1 and pool.warm_hits == 1
        # the bill: busy function-seconds plus the measured provision charge
        assert pool.cost_function_seconds() >= 0.75 + pool.provision_seconds \
            - 1e-6
        assert pool.provision_seconds > 0
    finally:
        pool.shutdown()


def test_pool_provision_floor_is_modeled_cold_start():
    t0 = time.perf_counter()
    pool = WorkerPool(max_workers=1, provision_s=3.0)
    try:
        _, cold = pool.lease()
        assert cold
        # a local spawn beats 3s; the model sleeps the remainder and bills
        # the floor
        assert time.perf_counter() - t0 >= 3.0
        assert pool.provision_seconds >= 3.0
    finally:
        pool.shutdown()


def test_pool_idle_reap_and_resize():
    pool = WorkerPool(max_workers=2, idle_reap_s=0.2)
    try:
        w, _ = pool.lease()
        first_pid = w.pid
        pool.release(w, busy_s=0.0)
        assert pool.size() == 1
        time.sleep(0.35)
        # lazy reap at the next interaction: the expired worker is retired
        # and the lease cold-starts a replacement
        w2, cold = pool.lease()
        assert cold and w2.pid != first_pid
        assert pool.reaped == 1 and pool.cold_starts == 2
        pool.release(w2, busy_s=0.0)
        # resize pre-warms to target, then shrinks back down
        assert pool.resize(2) == 2
        assert pool.cold_starts == 3
        assert pool.resize(1) == 1
        # grow is clamped at max_workers
        assert pool.resize(99) == 2
    finally:
        pool.shutdown()


def test_worker_pool_target_rule():
    # ceil(fanout / tasks_per_worker), clamped to [min_workers, max_workers]
    assert worker_pool_target(0, 5) == 1
    assert worker_pool_target(4, 0) == 1
    assert worker_pool_target(17, 0) == 5
    assert worker_pool_target(1024, 0) == 16
    assert worker_pool_target(1024, 0, max_workers=4) == 4


# -- full query on the process backend --------------------------------------------


def test_process_backend_query_matches_oracle_with_elastic_decision():
    fd, dd, ref = make_dist_tables()
    gc = GlobalController({n: 8 for n in range(4)})
    rt = Runtime(gc, invoker="process", max_workers=2)
    try:
        sched = QueryScheduler(rt, policy="fifo")
        sched.submit(QueryJob("q1", fd, dd, "static_merge"))
        res = sched.run()["q1"]
        assert res.ok, res.error
        np.testing.assert_allclose(res.sums, ref, atol=1e-3)
        # the sixth decision node bound on the runtime plane, last
        assert [n for n, _ in res.decisions] == \
            ["scan", "join", "exchange", "skew", "aggregate", "pipeline",
             "elastic", "tiering"]
        elastic = dict(res.decisions)["elastic"]
        assert elastic.func in ("grow", "shrink", "hold")
        assert elastic.scale >= 1
        # no leaked claims, and the pool actually reused warm workers
        assert sum(gc.used.values()) == 0
        stats = rt.invoker.pool.stats()
        assert stats["warm_hits"] > 0
        assert stats["cost_function_seconds"] > 0
    finally:
        rt.invoker.shutdown()


def test_workers_run_on_host_cpu_whatever_the_host_env(monkeypatch):
    """Spawned workers pin jax to the CPU before importing it: with the
    host's environment asking for a TPU (which a worker could never share
    with the host process), the query still runs in the workers and the
    host's environment is left as it was."""
    import os

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    fd, dd, ref = make_dist_tables(seed=11)
    gc = GlobalController({n: 8 for n in range(4)})
    rt = Runtime(gc, invoker="process", max_workers=1)
    try:
        got, _ = execute_query_runtime(fd, dd, QueryStrategy("static_merge"),
                                       runtime=rt)
        np.testing.assert_allclose(got, ref, atol=1e-3)
        assert rt.invoker.pool.stats()["cold_starts"] >= 1
    finally:
        rt.invoker.shutdown()
    assert os.environ["JAX_PLATFORMS"] == "tpu"


# -- SIGKILL chaos ----------------------------------------------------------------


def _run_killed_query(kills, seed=7):
    fd, dd, ref = make_dist_tables(seed=seed)
    gc = GlobalController({n: 8 for n in range(4)})
    rt = Runtime(gc, invoker="process", max_workers=2)
    FaultInjector(FaultPlan(worker_kills=list(kills))).install(rt)
    try:
        got, _ = execute_query_runtime(fd, dd, QueryStrategy("static_merge"),
                                       runtime=rt)
        np.testing.assert_allclose(got, ref, atol=1e-3)
        return rt, gc
    finally:
        rt.invoker.shutdown()


@pytest.mark.parametrize("when", ["body", "late"])
def test_worker_kill_heals_with_clean_slots(when):
    """A SIGKILLed worker surfaces as a crashed attempt, releases its slot
    claim, and the retry completes on a fresh worker. ``when="late"`` kills
    after the body ran — every write was still buffered worker-side, so the
    store sees none of them (the no-partial-writes invariant)."""
    rt, gc = _run_killed_query(
        [WorkerKillFault("scan_fact", index=1, when=when)])
    recs = [(r.status, r.attempt) for r in rt.metrics.records
            if r.name == "query/scan_fact/1"]
    assert ("crashed", 0) in recs and ("ok", 1) in recs
    assert sum(gc.used.values()) == 0
    assert ("worker-kill", "query/scan_fact/1") in rt.invoker.injector.injected
    # the healed store holds exactly one live write per scan partition
    assert sorted(rt.store.partitions("query", "scan_fact")) == [0, 1, 2, 3]


def test_worker_kill_mid_join_recovers_and_replaces_worker():
    """Killing a join worker mid-read exercises the host-side RPC path: the
    pipe EOF surfaces as WorkerKilledError, the poisoned worker is retired
    (never reused), and the retry runs on a replacement process."""
    rt, gc = _run_killed_query(
        [WorkerKillFault("join", index=0, when="body")], seed=3)
    recs = [(r.status, r.attempt) for r in rt.metrics.records
            if r.name == "query/join/0"]
    assert ("crashed", 0) in recs and ("ok", 1) in recs
    assert sum(gc.used.values()) == 0
    # a killed worker is replaced, not reused: at least one extra cold start
    assert rt.invoker.pool.cold_starts >= 2


if HAVE_HYPOTHESIS:
    _kill_strategy = st.lists(
        st.tuples(st.sampled_from(["scan_fact", "join", "partial_agg"]),
                  st.integers(0, 1), st.sampled_from(["body", "late"])),
        min_size=1, max_size=2, unique_by=lambda k: (k[0], k[1]))
else:                                    # pragma: no cover - shim path
    _kill_strategy = None


@settings(max_examples=3, deadline=None)
@given(kills=_kill_strategy)
def test_chaos_worker_kill_schedules_never_leak(kills):
    """Property: any small schedule of worker kills still completes with
    the oracle result, zero leaked controller slots, and one crashed record
    per fired kill."""
    plan = [WorkerKillFault(stage, index=idx, when=when)
            for stage, idx, when in kills]
    rt, gc = _run_killed_query(plan, seed=13)
    assert sum(gc.used.values()) == 0
    crashed = [r for r in rt.metrics.records if r.status == "crashed"]
    assert len(crashed) == len(rt.invoker.injector.injected)
    assert all(kind == "worker-kill"
               for kind, _ in rt.invoker.injector.injected)


# -- the simulator's cold-start twin ----------------------------------------------


def _sim_wave(provision_s, warm_pool, n=4, slots=4):
    gc = GlobalController({0: slots})
    sim = ClusterSim(gc, provision_s=provision_s, warm_pool=warm_pool)
    for i in range(n):
        sim.submit(SimTask(f"a/map1/{i}", "a", 1.0, node=0))
    return sim, sim.run()


def test_sim_cold_starts_vs_warm_pool():
    cold_sim, cold_out = _sim_wave(provision_s=2.0, warm_pool=0)
    warm_sim, warm_out = _sim_wave(provision_s=2.0, warm_pool=4)
    assert cold_sim.cold_starts == 4 and cold_sim.warm_hits == 0
    assert warm_sim.warm_hits == 4 and warm_sim.cold_starts == 0
    # provisioning sits on the critical path and on the bill
    assert warm_out["completion"]["a"] + 2.0 <= cold_out["completion"]["a"]
    assert warm_out["cost_function_seconds"]["a"] + 8.0 <= \
        cold_out["cost_function_seconds"]["a"] + 1e-9


def test_sim_warm_reuse_across_waves_and_prewarm_billing():
    # 1 slot serializes 3 tasks: first cold-starts, the rest lease warm
    sim, _ = _sim_wave(provision_s=2.0, warm_pool=0, n=3, slots=1)
    assert sim.cold_starts == 1 and sim.warm_hits == 2
    assert sim.pool == 1
    # prewarm (the elastic "grow" path) bills provision up front
    gc = GlobalController({0: 4})
    sim2 = ClusterSim(gc, provision_s=2.0)
    sim2.prewarm(3, app="a")
    assert sim2.pool == 3 and sim2.cold_starts == 3
    assert sim2.fn_seconds["a"] == pytest.approx(6.0)
    for i in range(3):
        sim2.submit(SimTask(f"a/map1/{i}", "a", 1.0, node=0))
    out = sim2.run()
    assert sim2.warm_hits == 3           # the fan-out leased warm
    assert out["completion"]["a"] == pytest.approx(1.0)


def test_sim_idle_reap_retires_warm_workers():
    gc = GlobalController({0: 1})
    sim = ClusterSim(gc, provision_s=2.0, idle_reap_s=0.5)
    sim.prewarm(2, app="a")
    assert sim.pool == 2 and sim.cold_starts == 2
    sim.now = 1.0          # sim time passes the reap window with no leases
    sim.submit(SimTask("a/map1/0", "a", 1.0, node=0))
    out = sim.run()
    # both expired warm workers were retired; the task cold-started fresh
    assert sim.reaped == 2 and sim.cold_starts == 3
    assert out["completion"]["a"] == pytest.approx(1.0 + 2.0 + 1.0)
