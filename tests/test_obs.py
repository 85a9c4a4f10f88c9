"""Observability: trace integrity, Chrome export round-trip, exact
critical paths on synthetic span DAGs, decision-audit diffing, metrics
compaction and the starved/error dashboard columns."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import (
    QueryStrategy,
    Table,
    execute_query_runtime,
    reference_query_numpy,
    synth_table,
)
from repro.analytics.planner import build_query_workflow
from repro.analytics.table import distribute
from repro.core.controllers import GlobalController
from repro.obs import (
    Span,
    Tracer,
    critical_path,
    get_audit_log,
    get_tracer,
    set_tracer,
    to_chrome_trace,
    validate_chrome_trace,
)
from repro.runtime import MetricsSink, QueryJob, QueryScheduler, Runtime
from repro.runtime.metrics import InvocationRecord


def make_dist_tables(rows=4096, keyspace=2048, dim_rows=512,
                     fact_nodes=4, dim_nodes=2, seed=1):
    fact = synth_table("f", rows, keyspace, seed=seed)
    dimc = synth_table("d", dim_rows, keyspace, seed=seed + 1,
                       unique_keys=True)
    dim = Table({**dimc.columns,
                 "cat": jnp.arange(dim_rows, dtype=jnp.int32) % 64})
    ref = reference_query_numpy(fact, dim)
    return (distribute(fact, range(fact_nodes), "A"),
            distribute(dim, range(dim_nodes), "B"), ref)


@pytest.fixture(autouse=True)
def fresh_obs():
    get_tracer().clear()
    get_audit_log().clear()
    yield
    get_tracer().clear()
    get_audit_log().clear()


# -- tracer mechanics ------------------------------------------------------------


def test_span_nesting_and_intra_thread_parenting():
    tr = Tracer()
    with tr.span("outer", "executor", trace="t") as outer:
        with tr.span("inner", "store") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace == "t"          # inherited from parent
    spans = tr.spans("t")
    assert [s.name for s in spans] == ["inner", "outer"]
    # nesting is temporal containment
    by = {s.name: s for s in spans}
    assert by["outer"].start <= by["inner"].start
    assert by["inner"].end <= by["outer"].end


def test_anchors_give_cross_thread_parents():
    tr = Tracer()
    root = tr.start("query/x", "scheduler", trace="x", parent=None)
    tr.anchor(("query", "x"), root)
    child = tr.start("stage/s", "executor", trace="x",
                     parent=tr.anchored(("query", "x")))
    assert child.parent_id == root.span_id
    tr.release_anchor(("query", "x"))
    assert tr.anchored(("query", "x")) is None
    tr.end(child)
    tr.end(root)


def test_ring_buffer_bounds_and_disabled_tracer():
    tr = Tracer(capacity=4)
    for i in range(7):
        with tr.span(f"s{i}", "store", trace="t"):
            pass
    assert len(tr.spans()) == 4
    assert [s.name for s in tr.spans()] == ["s3", "s4", "s5", "s6"]

    off = Tracer(enabled=False)
    with off.span("x", "store", trace="t") as sp:
        assert sp is None
    off.count("store_bytes/t", 5)
    assert off.spans() == [] and off.counters() == []
    assert off.start("x", "store") is None
    assert off.record("x", "store", 0.0) is None


def test_all_parents_live_in_buffer_after_real_query():
    fd, dd, _ = make_dist_tables()
    # static_merge shuffles both sides, so the kernel dispatch layer
    # (grouping_indices) fires inside the shuffle_write function bodies
    execute_query_runtime(fd, dd, QueryStrategy("static_merge"))
    spans = get_tracer().spans("query")
    assert spans, "a real query must leave spans"
    ids = {s.span_id for s in spans}
    dangling = [s for s in spans if s.parent_id is not None
                and s.parent_id not in ids]
    assert not dangling, [s.name for s in dangling]
    cats = {s.cat for s in spans}
    assert {"executor", "invoker", "store", "kernel"} <= cats
    # one non-store root: the executor's own query span (seed-time store
    # puts happen before any query root exists and stay roots)
    roots = [s for s in spans if s.parent_id is None and s.cat != "store"]
    assert [s.name for s in roots] == ["query/query"]


def test_chrome_trace_round_trip_with_scheduler():
    fd, dd, ref = make_dist_tables(rows=2048, dim_rows=256,
                                   fact_nodes=2, dim_nodes=1)
    gc = GlobalController({0: 4, 1: 4})
    rt = Runtime(gc, invoker="threads")
    sched = QueryScheduler(rt, policy="fair_share")
    sched.submit(QueryJob("obs_q", fd, dd, "static_hash", priority=3))
    res = sched.run()["obs_q"]
    assert res.ok, res.error
    np.testing.assert_allclose(res.sums, ref, atol=1e-3)

    trace = to_chrome_trace(get_tracer(), app="obs_q")
    info = validate_chrome_trace(json.dumps(trace))   # JSON round trip
    assert info["events"] > 0
    assert {"scheduler", "executor", "invoker", "store"} <= set(info["cats"])
    assert "store_bytes/obs_q" in info["counter_tracks"]
    assert any(t.startswith("slots/node") for t in info["counter_tracks"])
    # node processes + the control-plane process
    assert 1 in info["pids"] and any(p >= 10 for p in info["pids"])


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"no": "traceEvents"})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "pid": 1, "ts": -1, "dur": 1,
                              "name": "x", "tid": 0}]})


# -- critical path on synthetic span DAGs ----------------------------------------


def _stage(sid, name, deps, t0, t1):
    return Span(sid, "app", f"stage/{name}", "executor", t0, end=t1,
                attrs={"stage": name, "deps": list(deps)})


def _inv(sid, stage, t0, t1, node=0):
    return Span(sid, "app", f"app/{stage}/0", "invoker", t0, end=t1,
                node=node, attrs={"kind": "invocation", "stage": stage})


def test_critical_path_exact_on_synthetic_dag():
    # A (0-10) -> B (12-20); a non-bounding sibling A2 finishes earlier
    spans = [
        _stage(1, "A", (), 0.0, 10.0),
        _stage(2, "B", ("A",), 10.0, 20.0),
        _inv(3, "A", 0.0, 10.0),
        Span(4, "app", "app/A/1", "invoker", 0.0, end=4.0, node=1,
             attrs={"kind": "invocation", "stage": "A"}),
        _inv(5, "B", 12.0, 20.0, node=1),
        # store read inside the bounding B invocation: 3s transfer
        Span(6, "app", "get/A", "store", 13.0, end=16.0, parent_id=5),
    ]
    cp = critical_path(spans, app="app")
    assert [s.stage for s in cp.steps] == ["A", "B"]
    assert cp.steps[0].name == "app/A/0"          # max-end pred, not A/1
    assert cp.makespan == pytest.approx(20.0)
    assert cp.steps[1].queue == pytest.approx(2.0)   # 12 - 10 gap
    assert cp.steps[1].store == pytest.approx(3.0)
    assert cp.steps[1].compute == pytest.approx(5.0)
    assert cp.breakdown["compute"] == pytest.approx(15.0)
    assert cp.dominant == "compute"


def test_critical_path_slot_wait_bound():
    spans = [
        _stage(1, "A", (), 0.0, 30.0),
        _inv(2, "A", 0.0, 30.0),
        Span(3, "app", "slot_wait", "wait", 1.0, end=25.0, parent_id=2),
    ]
    cp = critical_path(spans, app="app")
    assert cp.dominant == "slot_wait"
    assert cp.breakdown["slot_wait"] == pytest.approx(24.0)
    assert cp.breakdown["compute"] == pytest.approx(6.0)


def test_critical_path_store_bound_and_batch_wait_inheritance():
    spans = [
        _stage(1, "A", (), 0.0, 20.0),
        # batch span owns the claim wait; its member owns the store time
        Span(2, "app", "batch/A@0", "invoker", 0.0, end=20.0, node=0,
             attrs={"kind": "batch", "stage": "A"}),
        Span(3, "app", "slot_wait", "wait", 0.0, end=2.0, parent_id=2),
        Span(4, "app", "app/A/0", "invoker", 2.0, end=20.0, node=0,
             parent_id=2, attrs={"kind": "invocation", "stage": "A"}),
        Span(5, "app", "put/out", "store", 5.0, end=17.0, parent_id=4),
    ]
    cp = critical_path(spans, app="app")
    assert cp.dominant == "store"
    assert cp.breakdown["store"] == pytest.approx(12.0)
    assert cp.breakdown["slot_wait"] == pytest.approx(2.0)  # inherited
    assert cp.breakdown["compute"] == pytest.approx(4.0)


def test_critical_path_overlapping_producer_consumer():
    """Pipelined launch: the consumer starts before either producer ends.
    The path follows the earliest-released producer with a zero queue gap,
    and the frontier-walk breakdown attributes each instant once, so the
    phase totals still sum to the makespan despite the overlap."""
    spans = [
        _stage(1, "A", (), 0.0, 12.0),
        _stage(2, "B", ("A",), 4.0, 14.0),
        _inv(3, "A", 0.0, 10.0),                       # released first
        Span(4, "app", "app/A/1", "invoker", 0.0, end=12.0, node=1,
             attrs={"kind": "invocation", "stage": "A"}),
        _inv(5, "B", 4.0, 14.0, node=1),               # overlaps both A's
        Span(6, "app", "get/A", "store", 5.0, end=10.0, parent_id=5),
    ]
    cp = critical_path(spans, app="app")
    assert [s.stage for s in cp.steps] == ["A", "B"]
    assert cp.steps[0].name == "app/A/0"      # earliest end, not latest
    assert cp.steps[1].queue == pytest.approx(0.0)   # overlap -> no idle
    assert cp.makespan == pytest.approx(14.0)
    # B extends the frontier only over 10..14 (w=4 of its 10s span), its
    # 5s store and 5s compute scale by 0.4 into that window
    assert cp.breakdown["store"] == pytest.approx(2.0)
    assert cp.breakdown["compute"] == pytest.approx(12.0)
    assert sum(cp.breakdown.values()) == pytest.approx(cp.makespan)


def test_critical_path_none_without_invocations():
    assert critical_path([], app="x") is None
    assert critical_path([_stage(1, "A", (), 0.0, 1.0)], app="app") is None


# -- decision audit --------------------------------------------------------------


def test_audit_entries_match_workflow_sequence():
    fd, dd, _ = make_dist_tables(rows=2048, dim_rows=256, seed=3)
    wf = build_query_workflow(QueryStrategy("dynamic"))
    execute_query_runtime(fd, dd, QueryStrategy("dynamic"), workflow=wf)
    run = wf.last_run
    want = [(stage, d.func) for stage, d in run.sequence]
    got = get_audit_log().sequence("query", nodes=[s for s, _ in want])
    assert got == want
    # the snapshot carries candidates + the upstream bindings
    entries = get_audit_log().entries("query")
    assert all(e.candidates for e in entries
               if e.node in {s for s, _ in want})
    join = next(e for e in entries if e.node == "join")
    assert ("scan", "scan_filter") in join.prior
    assert "A_scanned" in join.data_dist     # observed post-scan dist
    assert join.format()                     # human-readable, non-empty


def test_audit_log_bounded_and_clearable():
    log = get_audit_log()
    fd, dd, _ = make_dist_tables(rows=2048, dim_rows=256, seed=4)
    execute_query_runtime(fd, dd, QueryStrategy("static_hash"))
    assert log.entries("query")
    log.clear()
    assert log.entries() == []


# -- metrics satellites ----------------------------------------------------------


def _rec(stage, status, t0=0.0, t1=1.0, name=None):
    return InvocationRecord(name or f"a/{stage}/0", "a", stage, "f", 0, 0,
                            status, t0, t1)


def test_stage_metrics_counts_starved_and_error():
    sink = MetricsSink()
    sink.record(_rec("s", "ok"))
    sink.record(_rec("s", "starved", name="a/s/1"))
    sink.record(_rec("s", "error", name="a/s/2"))
    m = sink.by_stage("a")["s"]
    assert (m.ok, m.starved, m.error) == (1, 1, 1)
    fb = sink.profile_feedback("a")
    assert fb["s.starved"] == 1 and fb["s.error"] == 1


def test_format_table_sorted_by_first_start_with_totals():
    sink = MetricsSink()
    sink.record(_rec("late", "ok", t0=10.0, t1=11.0))
    sink.record(_rec("early", "ok", t0=0.0, t1=2.0))
    sink.record(_rec("early", "starved", t0=1.0, t1=1.0, name="a/early/1"))
    table = sink.format_table("a")
    lines = table.splitlines()
    order = [ln.split()[0] for ln in lines[1:]]
    assert order == ["early", "late", "TOTAL"]
    total = lines[-1].split()
    assert total[1] == "3"                   # invocations
    assert total[3] == "1"                   # starved column
    assert "stv" in lines[0] and "err" in lines[0]


def test_metrics_clear_per_app_and_scheduler_compaction():
    sink = MetricsSink()
    sink.record(_rec("s", "ok"))
    sink.record(InvocationRecord("b/s/0", "b", "s", "f", 0, 0, "ok", 0, 1))
    assert sink.clear(app="a") == 1
    assert [r.app for r in sink.records] == ["b"]
    assert sink.clear() == 1 and sink.records == []

    fd, dd, ref = make_dist_tables(rows=2048, dim_rows=256, seed=6,
                                   fact_nodes=2, dim_nodes=1)
    gc = GlobalController({0: 4, 1: 4})
    rt = Runtime(gc, invoker="threads")
    sched = QueryScheduler(rt, policy="fair_share", compact_metrics=True)
    sched.submit(QueryJob("cq", fd, dd, "static_hash"))
    res = sched.run()["cq"]
    assert res.ok, res.error
    np.testing.assert_allclose(res.sums, ref, atol=1e-3)
    # raw records compacted away, per-stage snapshot preserved
    assert rt.metrics.for_app("cq") == []
    assert res.stages and res.stages["final_agg"].ok == 1


def test_no_orphan_store_spans_in_pipelined_run():
    """Trace integrity across helper threads: a pipelined run issues store
    reads from ``PrefetchHandle`` background threads, whose spans must
    parent (via ``Tracer.adopt``) into the spawning invocation — never
    surface as orphan store-layer roots."""
    get_tracer().clear()
    fd, dd, ref = make_dist_tables(seed=11)
    got, _ = execute_query_runtime(fd, dd, QueryStrategy("static_merge"),
                                   invoker="threads", pipeline=True)
    np.testing.assert_allclose(got, ref, atol=1e-3)
    spans = get_tracer().spans("query")
    assert spans
    ids = {s.span_id for s in spans}
    dangling = [s for s in spans if s.parent_id is not None
                and s.parent_id not in ids]
    assert not dangling, [s.name for s in dangling]
    root = next(s for s in spans if s.name == "query/query")
    # seed-time puts predate the query root and the caller's result fetch
    # postdates it — both legitimately stay roots; every store span issued
    # while the query ran must have a parent
    orphans = [s for s in spans if s.cat == "store"
               and s.parent_id is None
               and root.start <= s.start <= root.end]
    assert not orphans, [s.name for s in orphans]


# -- overhead / disabled end-to-end ----------------------------------------------


def test_query_runs_clean_with_tracer_disabled(monkeypatch):
    entered = []
    monkeypatch.setattr(Tracer, "annotate",
                        staticmethod(lambda label, attrs: entered.append(
                            label)))
    prev = set_tracer(Tracer(enabled=False))
    try:
        fd, dd, ref = make_dist_tables(rows=2048, dim_rows=256, seed=8)
        got, _ = execute_query_runtime(fd, dd, QueryStrategy("static_merge"))
        np.testing.assert_allclose(got, ref, atol=1e-3)
        assert get_tracer().spans() == []
        # the profiler mirror is never entered by a disabled tracer
        assert entered == []
    finally:
        set_tracer(prev)


# -- planner, host<->device and profiler-clock spans -----------------------------


def test_obs_imports_no_jax():
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    code = "import sys, repro.obs; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**os.environ, "PYTHONPATH": src})


def test_unnested_span_keeps_the_enclosing_parent():
    tr = Tracer()
    with tr.span("inv", "invoker", trace="t") as inv:
        with tr.span("attempt/0", "invoker", nest=False) as att:
            with tr.span("get/x", "store") as get:
                pass
    assert att.parent_id == inv.span_id
    assert get.parent_id == inv.span_id        # not the attempt's
    assert att.start <= get.start and get.end <= att.end


def test_mirror_sees_every_context_span_with_its_label(monkeypatch):
    seen = []

    class Mirror:
        def __init__(self, label, attrs):
            self.label, self.attrs = label, attrs

        def __enter__(self):
            seen.append(("enter", self.label, self.attrs.get("func")))

        def __exit__(self, *exc):
            seen.append(("exit", self.label, None))

    monkeypatch.setattr(Tracer, "annotate", staticmethod(Mirror))
    tr = Tracer()
    with tr.span("a/s/0", "invoker", trace="t", func="f"):
        with tr.span("xfer/d2h", "xfer", nest=False):
            pass
    tr.record("slot_wait", "wait", 0.0)        # retroactive: not mirrored
    assert seen == [("enter", "repro:invoker:a/s/0", "f"),
                    ("enter", "repro:xfer:xfer/d2h", None),
                    ("exit", "repro:xfer:xfer/d2h", None),
                    ("exit", "repro:invoker:a/s/0", None)]


def test_transfer_helpers_span_waits_and_copies():
    from repro.kernels import ops as kops

    tr = Tracer()
    prev = set_tracer(tr)
    try:
        dev = {"k": jnp.arange(8, dtype=jnp.int32), "v": jnp.ones(8)}
        with tr.span("q/s/0", "invoker", trace="q", func="fn",
                     kind="invocation") as inv:
            host = kops.host_copy(dev, "site")
            back = kops.device_copy(host)
            same = kops.device_copy(dev)           # already on the device
            kops.device_wait(back, "put")
            kops.host_copy(host, "noop")           # already on the host
    finally:
        set_tracer(prev)
    assert list(host) == ["k", "v"]
    assert all(isinstance(v, np.ndarray) for v in host.values())
    assert same is dev
    np.testing.assert_array_equal(back["k"], dev["k"])
    spans = {s.name: s for s in tr.spans("q") if s.name != "q/s/0"}
    assert sorted(spans) == ["sync/put", "sync/site", "xfer/d2h", "xfer/h2d"]
    for s in spans.values():
        assert s.parent_id == inv.span_id and s.attrs["func"] == "fn"
    assert spans["xfer/d2h"].attrs["bytes"] == 8 * 4 + 8 * 4
    assert spans["xfer/h2d"].attrs["bytes"] == 8 * 4 + 8 * 4
    assert spans["sync/site"].end <= spans["xfer/d2h"].start


def test_planner_spans_nest_decisions_under_the_query_root():
    fd, dd, ref = make_dist_tables(rows=2048, dim_rows=256, seed=5)
    wf = build_query_workflow(QueryStrategy("static_merge"))
    got, _ = execute_query_runtime(fd, dd, QueryStrategy("static_merge"),
                                   workflow=wf)
    np.testing.assert_allclose(got, ref, atol=1e-3)
    spans = get_tracer().spans("query")
    by_id = {s.span_id: s for s in spans}
    root = next(s for s in spans if s.name == "query/query")
    plans = [s for s in spans if s.name.startswith("plan/")]
    assert {s.cat for s in plans} == {"planner"}
    assert "plan/initial" in {s.name for s in plans}
    assert all(s.parent_id == root.span_id for s in plans)
    # every stage the planner materialised is counted on its span
    stages = [s for s in spans if s.name.startswith("stage/")]
    assert sum(s.attrs["admitted"] for s in plans) == len(stages)
    # each bound decision is one decide/<node> span inside a plan span,
    # carrying the value the audit log records
    decides = [s for s in spans if s.name.startswith("decide/")]
    assert [(s.name[len("decide/"):], s.attrs["value"])
            for s in sorted(decides, key=lambda s: s.start)] == \
        [(n, d.func) for n, d in wf.last_run.sequence]
    assert all(by_id[s.parent_id].name.startswith("plan/") for s in decides)
    # the bodies' host<->device traffic is spanned under its invocation
    # (directly, or inside the join's dispatch span), tagged with its func
    body = [s for s in spans if s.cat in ("xfer", "sync", "host")]
    assert {s.name for s in body} >= {
        "xfer/d2h", "xfer/h2d", "sync/put", "sync/mask_rows",
        "sync/shuffle_write", "sync/join_idx", "host/sketch_verify"}
    for s in body:
        inv = by_id[s.parent_id]
        while inv.attrs.get("kind") != "invocation":
            assert inv.cat == "kernel", (s.name, inv.name)
            inv = by_id[inv.parent_id]
        assert s.attrs["func"] == inv.attrs["func"]


def _with_body_children(spans, base_id):
    """The same DAG with xfer/sync children under each invocation."""
    out, sid = list(spans), base_id
    for s in spans:
        if s.attrs.get("kind") != "invocation":
            continue
        mid = (s.start + s.end) / 2
        out.append(Span(sid, "app", "sync/put", "sync", s.start, end=mid,
                        parent_id=s.span_id))
        out.append(Span(sid + 1, "app", "xfer/d2h", "xfer", mid, end=s.end,
                        parent_id=s.span_id))
        sid += 2
    return out


@pytest.mark.parametrize("dag", ["exact", "store_bound", "overlapping"])
def test_body_spans_leave_the_critical_path_unchanged(dag):
    spans = {
        "exact": [_stage(1, "A", (), 0.0, 10.0),
                  _stage(2, "B", ("A",), 10.0, 20.0),
                  _inv(3, "A", 0.0, 10.0), _inv(5, "B", 12.0, 20.0, node=1),
                  Span(6, "app", "get/A", "store", 13.0, end=16.0,
                       parent_id=5)],
        "store_bound": [
            _stage(1, "A", (), 0.0, 20.0),
            Span(2, "app", "batch/A@0", "invoker", 0.0, end=20.0, node=0,
                 attrs={"kind": "batch", "stage": "A"}),
            Span(3, "app", "slot_wait", "wait", 0.0, end=2.0, parent_id=2),
            Span(4, "app", "app/A/0", "invoker", 2.0, end=20.0, node=0,
                 parent_id=2, attrs={"kind": "invocation", "stage": "A"}),
            Span(5, "app", "put/out", "store", 5.0, end=17.0, parent_id=4)],
        "overlapping": [_stage(1, "A", (), 0.0, 12.0),
                        _stage(2, "B", ("A",), 4.0, 14.0),
                        _inv(3, "A", 0.0, 10.0),
                        _inv(5, "B", 4.0, 14.0, node=1),
                        Span(6, "app", "get/A", "store", 5.0, end=10.0,
                             parent_id=5)],
    }[dag]
    plain = critical_path(spans, app="app")
    traced = critical_path(_with_body_children(spans, 100), app="app")
    assert traced.breakdown == plain.breakdown
    assert [s.to_dict() for s in traced.steps] == \
        [s.to_dict() for s in plain.steps]


def test_profiler_annotations_mirror_the_program_spans(tmp_path):
    """Under ``jax.profiler.trace`` the program's context spans are host
    annotations of the ``.xplane.pb``, named ``repro:<cat>:<name>``, on
    the trace's clock: tied by one marker, as the benchmark ties its
    window, each agrees with its ``perf_counter`` span within 1 ms."""
    import time

    import jax
    from jax.profiler import ProfileData

    fd, dd, ref = make_dist_tables(rows=2048, dim_rows=256, seed=9)
    execute_query_runtime(fd, dd, QueryStrategy("static_merge"))  # compile
    get_tracer().clear()
    jax.profiler.start_trace(str(tmp_path))
    t_tie = time.perf_counter()
    with jax.profiler.TraceAnnotation("test/tie"):
        pass
    got, _ = execute_query_runtime(fd, dd, QueryStrategy("static_merge"))
    jax.profiler.stop_trace()
    np.testing.assert_allclose(got, ref, atol=1e-3)

    (path,) = tmp_path.glob("**/*.xplane.pb")
    ann, tie_ns = {}, None
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "test/tie":
                    tie_ns = e.start_ns
                elif e.name.startswith("repro:"):
                    ann.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert tie_ns is not None

    def mirrored(s):
        return s.cat in ("planner", "xfer", "sync") or \
            s.name.split("/")[0] in ("get", "put", "put_many") or \
            s.attrs.get("kind") == "invocation"

    want: dict = {}
    for s in get_tracer().spans():
        if mirrored(s):
            want.setdefault(f"repro:{s.cat}:{s.name}", []).append(s)
    cats = {label.split(":")[1] for label in want}
    assert {"planner", "xfer", "sync", "store", "invoker"} <= cats
    assert any(label.startswith("repro:planner:decide/") for label in want)
    for label, spans in want.items():
        got = sorted(ann.get(label, ()))
        assert len(got) == len(spans), label
        for s, (lo, hi) in zip(sorted(spans, key=lambda s: s.start), got):
            assert abs((lo - tie_ns) * 1e-9 - (s.start - t_tie)) < 1e-3
            assert abs((hi - tie_ns) * 1e-9 - (s.end - t_tie)) < 1e-3
