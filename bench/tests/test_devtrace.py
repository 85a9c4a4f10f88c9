"""The trace reduction, on a small trace recorded on a TPU v5e (a slice of
a traced unit of sf10.uniform.sm, saved by ``record_slice`` below), and
on a trace recorded here on the CPU, which has no device plane."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import devtrace

DATA = Path(__file__).resolve().parent / "data" / "tpu_trace_slice.json"


def record_slice(trace: devtrace.Trace, lo: float, hi: float) -> dict:
    """The events of ``trace`` that overlap ``[lo, hi]``, as JSON."""
    def evs(d):
        return {p: [[e.name, e.start_ns, e.dur_ns, e.module] for e in v
                    if e.end_ns > lo and e.start_ns < hi]
                for p, v in d.items()}
    return {"ops": evs(trace.ops), "modules": evs(trace.modules),
            "markers": {devtrace.WINDOW_START: lo, devtrace.WINDOW_END: hi}}


def from_json(d: dict) -> devtrace.Trace:
    def evs(x):
        return {p: [devtrace.Event(n, s, t, m) for n, s, t, m in v]
                for p, v in x.items()}
    return devtrace.Trace(evs(d["ops"]), evs(d["modules"]), d["markers"])


@pytest.fixture(scope="module")
def recorded():
    return from_json(json.loads(DATA.read_text()))


def _grid_busy(trace, lo, hi, step):
    """Busy time by brute force: the share of grid points inside an op."""
    plane = sorted(trace.ops)[0]
    t = np.arange(lo, hi, step) + step / 2
    inside = np.zeros(t.shape, bool)
    for e in trace.ops[plane]:
        inside |= (t >= e.start_ns) & (t < e.end_ns)
    return inside.sum() * step


def test_busy_union_matches_brute_force(recorded):
    lo, hi = devtrace.window_ns(recorded)
    busy = devtrace.busy_ns(recorded, lo, hi)[0]
    step = (hi - lo) / 200_000
    assert busy == pytest.approx(_grid_busy(recorded, lo, hi, step),
                                 abs=4 * step * len(recorded.ops[
                                     sorted(recorded.ops)[0]]) ** 0.5 + step)
    assert 0 < busy < hi - lo


def test_gaps_and_busy_tile_the_window(recorded):
    lo, hi = devtrace.window_ns(recorded)
    gaps = devtrace.idle_gaps(recorded, lo, hi)
    busy = devtrace.busy_ns(recorded, lo, hi)[0]
    assert sum(b - a for a, b in gaps) + busy == pytest.approx(hi - lo)
    lengths = [b - a for a, b in gaps]
    assert lengths == sorted(lengths, reverse=True)


def test_top_ops_are_sorted_and_bounded(recorded):
    lo, hi = devtrace.window_ns(recorded)
    top = devtrace.top_ops(recorded, lo, hi)
    assert 0 < len(top) <= 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    # an op's seconds can overlap others' but never exceed the window
    assert max(secs) <= (hi - lo) * 1e-9


def test_merged_intervals():
    assert devtrace.merged([(5, 6), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 6)]
    assert devtrace.clipped([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]


def test_cpu_trace_has_no_device_plane(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_START):
        pass
    jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_END):
        pass
    jax.profiler.stop_trace()
    tr = devtrace.load(devtrace.find_xplane(str(tmp_path)))
    assert tr.ops == {}
    lo, hi = devtrace.window_ns(tr)
    assert hi > lo


def test_attach_modules_by_containment():
    mods = [devtrace.Event("jit_a", 0, 10), devtrace.Event("jit_b", 20, 10)]
    ops = [devtrace.Event("%x", 1, 2), devtrace.Event("%y", 15, 1),
           devtrace.Event("%z", 25, 1)]
    devtrace._attach_modules(ops, mods)
    assert [o.module for o in ops] == ["jit_a", "", "jit_b"]
    assert devtrace.op_name("%fusion.10 = s32[8] fusion(%p)") == "%fusion.10"
    assert devtrace.module_name("jit_f(5123)") == "jit_f"


@pytest.mark.parametrize("metric,span,module,op", [
    ("histogram_roofline", "kernel/histogram", "jit_partition_histogram",
     "%partition_histogram"),
    ("destinations_roofline", "kernel/grouping", "jit__grouping_pallas",
     "%partition_destinations")])
def test_kernel_roofline_on_the_recorded_slice(recorded, metric, span,
                                               module, op):
    from benchlib import cost
    from benchlib.kernels import kernel_events
    from benchlib.peaks import peaks_for
    from benchlib.readers import RunView, load_reader

    rows, buckets = 3_600_124, 4
    run = RunView([], 1.0, 1.0, trace=recorded,
                  trace_window=devtrace.window_ns(recorded),
                  peaks=peaks_for("TPU v5 lite"))
    events = kernel_events(run, module, op)
    assert events
    read = load_reader(metric)
    run.kernel_calls = [(span, {"rows": rows, "buckets": buckets,
                                "path": "pallas"})] * len(events)
    share = read(run)
    cost_fn = cost.histogram_cost if "histogram" in metric \
        else cost.destinations_cost
    extra = 0 if "histogram" in metric else 1
    least = len(events) * cost_fn(rows, buckets + extra)["bytes"] / 819e9
    assert share == pytest.approx(
        100 * least / (sum(e.dur_ns for e in events) * 1e-9))
    # a dispatch the trace does not hold makes the pairing unsound
    run.kernel_calls = run.kernel_calls + run.kernel_calls[:1]
    assert read(run) is None
