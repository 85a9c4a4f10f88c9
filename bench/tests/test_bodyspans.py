"""The planner and function-body readings (``benchlib/bodyspans.py``) on
synthetic spans, the readers' ``None`` on a program without such spans,
and the device-trace metrics of the recorded TPU slice, unchanged."""

import json

import pytest

from benchlib import bodyspans, devtrace
from benchlib.readers import RunView, load_reader
from repro.obs import Span

NEW = ("plan.decide_s", "inv.critpath_xfer_s", "inv.critpath_sync_s")


def _stage(sid, app, name, deps, t0, t1):
    return Span(sid, app, f"stage/{name}", "executor", t0, end=t1,
                attrs={"stage": name, "deps": list(deps)})


def _inv(sid, app, stage, t0, t1, i=0):
    return Span(sid, app, f"{app}/{stage}/{i}", "invoker", t0, end=t1,
                attrs={"kind": "invocation", "stage": stage, "func": "f"})


def _query(app, base, body=True):
    """A -> B on the critical path: plan spans between them, and (with
    ``body``) sync and xfer spans inside both invocations, one xfer
    nested in a kernel span. A2 is a sibling off the path."""
    b = base
    spans = [
        Span(b, app, f"query/{app}", "executor", 0.0, end=20.0),
        Span(b + 1, app, "plan/initial", "planner", 0.0, end=0.5,
             parent_id=b),
        Span(b + 2, app, "decide/scan", "planner", 0.1, end=0.4,
             parent_id=b + 1),
        _stage(b + 3, app, "A", (), 0.5, 10.0),
        _stage(b + 4, app, "B", ("A",), 11.0, 20.0),
        _inv(b + 5, app, "A", 1.0, 10.0),
        _inv(b + 6, app, "A", 1.0, 5.0, i=1),
        Span(b + 7, app, "plan/A", "planner", 10.0, end=11.0, parent_id=b),
        _inv(b + 8, app, "B", 12.0, 20.0),
        Span(b + 9, app, "get/A", "store", 12.0, end=13.0, parent_id=b + 8),
    ]
    if body:
        spans += [
            Span(b + 10, app, "sync/put", "sync", 2.0, end=6.0,
                 parent_id=b + 5),
            Span(b + 11, app, "xfer/d2h", "xfer", 6.0, end=7.0,
                 parent_id=b + 5),
            Span(b + 12, app, "kernel/x", "kernel", 14.0, end=17.0,
                 parent_id=b + 8),
            Span(b + 13, app, "xfer/h2d", "xfer", 14.5, end=16.5,
                 parent_id=b + 12),
            Span(b + 14, app, "xfer/d2h", "xfer", 16.0, end=17.0,
                 parent_id=b + 8),
            # off the critical path: counts nowhere
            Span(b + 15, app, "xfer/d2h", "xfer", 1.0, end=5.0,
                 parent_id=b + 6),
        ]
    return spans


def test_plan_seconds_per_query():
    spans = _query("q0", 1) + _query("q1", 100)
    assert bodyspans.plan_seconds(spans) == pytest.approx(1.5)
    # decide spans are inside plan spans: never counted twice
    only_decide = [s for s in spans if not s.name.startswith("plan/")]
    assert bodyspans.plan_seconds(only_decide) == 0.0
    assert bodyspans.plan_seconds(
        [s for s in spans if s.cat != "planner"]) is None


def test_critpath_inside_counts_the_path_only():
    spans = _query("q0", 1)
    # A/0 (1..10): 1 s xfer, 4 s sync; B (12..20): xfer 14.5..17 (union
    # of a nested 14.5..16.5 and 16..17), no sync
    assert bodyspans.critpath_inside(spans, "xfer") == pytest.approx(3.5)
    assert bodyspans.critpath_inside(spans, "sync") == pytest.approx(4.0)
    two = spans + _query("q1", 100, body=True)
    assert bodyspans.critpath_inside(two, "xfer") == pytest.approx(3.5)


def test_critpath_inside_is_zero_without_time_and_none_without_spans():
    spans = _query("q0", 1)
    no_sync = [s for s in spans if s.cat != "sync"]
    assert bodyspans.critpath_inside(no_sync, "sync") == 0.0
    plain = _query("q0", 1, body=False)
    assert bodyspans.critpath_inside(plain, "xfer") is None
    assert bodyspans.critpath_inside([], "sync") is None


def test_critpath_inside_scales_overlapped_steps():
    """A pipelined consumer that starts before its producer ends extends
    the makespan over part of its span only; its body time scales with it,
    as the critical path's compute does."""
    app = "p"
    spans = [
        _stage(1, app, "A", (), 0.0, 10.0),
        _stage(2, app, "B", ("A",), 4.0, 14.0),
        _inv(3, app, "A", 0.0, 10.0),
        _inv(4, app, "B", 4.0, 14.0),
        Span(5, app, "xfer/h2d", "xfer", 5.0, end=10.0, parent_id=4),
    ]
    # B extends the frontier over 10..14: 4 of its 10 s, so 5 s * 0.4
    assert bodyspans.critpath_inside(spans, "xfer") == pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW)
def test_readers_take_the_last_units_spans(name, monkeypatch):
    run = RunView([], 1.0, 1.0)
    want = {"plan.decide_s": 1.5, "inv.critpath_xfer_s": 3.5,
            "inv.critpath_sync_s": 4.0}[name]
    monkeypatch.setattr(bodyspans, "last_unit_spans",
                        lambda: _query("q0", 1))
    assert load_reader(name)(run) == pytest.approx(want)
    assert load_reader(name + ".shared")(run) == pytest.approx(want)
    # a program without the spans: the metric is left out, nothing raises
    monkeypatch.setattr(bodyspans, "last_unit_spans",
                        lambda: [s for s in _query("q0", 1, body=False)
                                 if s.cat != "planner"])
    assert load_reader(name)(run) is None


def test_recorded_slice_reads_as_before():
    """The device-trace metrics of the recorded TPU slice are the values
    they read before the program's spans were mirrored to the profiler."""
    from test_devtrace import DATA, from_json

    trace = from_json(json.loads(DATA.read_text()))
    win = devtrace.window_ns(trace)
    run = RunView([], 1.0, 1.0, trace=trace, trace_window=win)
    assert load_reader("dev.idle_share")(run) == \
        pytest.approx(0.02082224127963883, rel=1e-12)
    assert devtrace.busy_ns(trace, *win) == [328023166.0]
    assert devtrace.idle_gaps(trace, *win)[0] == (755240338.0, 755243560.0)
