"""Benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on a TPU only: on any other platform, or with fewer chips than the
cell asks for, it says so on standard error and exits 3 with no result.

A run, in order:

1. set-up (``setup_s``, from the start of the process): JAX and the chip,
   the compile cache at the checkout's fixed path, the cell's tables made
   on the device from ``--seed`` by the configuration's app
   (``bench/apps/``), and the warm-up units of the cell's traffic (queries
   or waves of its loop, ``bench/loops/``; ``warmup_units``, default 1),
   which compile or load from the cache every program the window runs;
2. the window: whole units of the traffic until ``--seconds`` have passed,
   ending at the end of the last one. No program compiles here; the count
   is printed;
3. the check, once the window has closed and the peak memory is read:
   every query's answer against the app's float64 reference of its own
   tables, by the app's error;
4. the metrics, each from its reader in ``bench/metrics/``: the cell's
   end-to-end metrics with ``--trace 0`` (the program's tracer off, no
   profiler), its per-layer metrics with ``--trace 1`` (tracer on, the
   first unit of the window under the JAX profiler).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
The same checks are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

EXIT_NO_CHIP = 3


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileWatch:
    """Counts every program JAX builds or loads from its cache, with the
    host-clock interval of each."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.intervals: list[tuple[float, float]] = []
        self._offset = time.time() - time.perf_counter()
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.intervals.append((start - self._offset,
                                   end - self._offset))


def _quiet_profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-line Python events
    opts.host_tracer_level = 1        # host annotations (the markers)
    return opts


def run_window(dep, seconds: float, traced: bool, trace_dir: str | None):
    """Whole units until ``seconds`` have passed. With tracing, each unit's
    spans are summarized before the next one clears the ring buffer, and
    the first unit runs under the profiler."""
    import jax

    from benchlib import devtrace, spans as span_sum
    from repro.obs import get_tracer

    tracer = get_tracer()
    queries, prof = [], {}
    unit = 1
    t0 = time.perf_counter()
    while True:
        profile = traced and unit == 1
        if traced:
            tracer.clear()
        if profile:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_quiet_profiler_options())
            prof["t_lo"] = time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_START):
                pass
        recs = dep.run_unit(unit)
        if profile:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_END):
                pass
            jax.profiler.stop_trace()
        if traced:
            spans = tracer.spans()
            if len(spans) >= tracer.capacity:
                print(f"warning: unit {unit} filled the tracer's ring buffer "
                      f"({tracer.capacity} spans); its summaries are partial",
                      file=sys.stderr)
            for r in recs:
                r.spans = span_sum.summarize(spans, r.app)
            if profile:
                prof["spans"] = spans
                prof["kernel_calls"] = span_sum.kernel_calls(spans)
        queries += recs
        unit += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return queries, time.perf_counter() - t0, prof


def check(dep, queries, warmup, limits) -> dict:
    """Every query's answer against the app's reference of its tenant's
    tables. Returns the checks, each ``{"value", "limit"}``, and the
    failed count."""
    app = dep.app
    refs = [app.reference(dep.config, t) for t in dep.tenants]
    limit = float(limits["rel_err"])
    errs, failed = [], 0
    for q in queries:
        err = app.error(q.answer, refs[q.tenant])
        errs.append(err)
        if q.error is not None or not err <= limit:
            failed += 1
    warm_bad = sum(1 for q in warmup if q.error is not None or
                   not app.error(q.answer, refs[q.tenant]) <= limit)
    return {"failed": failed, "errors": [q.error for q in queries + warmup
                                         if q.error][:3],
            "checks": {
                "rel_err_max": {"value": max(errs, default=float("inf")),
                                "limit": limit},
                "failed_queries": {"value": failed, "limit": 0},
                "failed_warmup": {"value": warm_bad, "limit": 0}}}


def breakdown(prof: dict, trace, lo: float, hi: float, watch) -> dict:
    """The device operations that took most time, and the longest idle
    gaps labelled by what the host was doing in them."""
    from benchlib import devtrace, spans as span_sum

    def host_t(ns: float) -> float:
        return prof["t_lo"] + (ns - lo) * 1e-9

    gaps = []
    for g_lo, g_hi in devtrace.idle_gaps(trace, lo, hi)[:10]:
        t = host_t((g_lo + g_hi) / 2)
        label = "compile" if any(a <= t <= b for a, b in watch.intervals) \
            else span_sum.host_label(prof["spans"], t)
        gaps.append([label, (g_hi - g_lo) * 1e-9])
    return {"device_ops": [list(kv) for kv in devtrace.top_ops(trace, lo, hi)],
            "idle_gaps": gaps}


def main(argv=None, cell=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler's trace of a --trace 1 run here")
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.perf_counter()

    from benchlib.cell import load_cell

    cell = cell or load_cell(args.workload)
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if require_chip and (info["platform"] != "tpu"
                         or info["count"] < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {info['count']} device(s) of platform "
              f"{info['platform']!r}", file=sys.stderr)
        return EXIT_NO_CHIP

    import jax

    from benchlib import devtrace, drive
    from benchlib.peaks import peaks_for
    from benchlib.readers import RunView, read_all
    from repro.compile_cache import enable_compile_cache
    from repro.obs import Tracer, set_tracer

    peaks = peaks_for(info["kind"]) if require_chip else None
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    set_tracer(Tracer(enabled=bool(args.trace)))
    watch = CompileWatch()

    dep = drive.Deployment(cell.config, cell.traffic, args.seed)
    warmup = []
    for unit in range(int(cell.traffic.get("warmup_units", 1))):
        warmup += dep.run_unit(-1 - unit)
    setup_s = time.perf_counter() - t_start
    compiles_setup = watch.count
    print(f"setup: {setup_s:.3f} s, {compiles_setup} programs built or "
          f"loaded", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    try:
        queries, window_s, prof = run_window(dep, args.seconds,
                                             bool(args.trace), trace_dir)
        compiles_window = watch.count - compiles_setup
        stats = jax.devices()[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        view = RunView(queries, window_s, setup_s, peaks=peaks)
        device = {**info, "memory_peak_bytes": peak}
        extra = {}
        if args.trace:
            path = devtrace.find_xplane(trace_dir)
            trace = devtrace.load(path) if path else devtrace.Trace()
            if args.keep_trace and path:
                Path(args.keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            win = devtrace.window_ns(trace)
            view.kernel_calls = prof.get("kernel_calls", [])
            view.trace, view.trace_window = trace, win
            if win is not None:
                lo, hi = win
                busy = devtrace.busy_ns(trace, lo, hi)
                device["busy_s"] = sum(busy) / max(1, len(busy)) * 1e-9
                device["window_s"] = (hi - lo) * 1e-9
                extra["breakdown"] = breakdown(prof, trace, lo, hi, watch)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    print(f"window: {window_s:.3f} s, {len(queries)} queries, "
          f"{compiles_window} programs built or loaded in the window",
          flush=True)
    print(f"peak device memory: {peak} bytes", flush=True)
    for decisions in sorted({q.decisions for q in warmup + queries}):
        print("decisions: " + " ".join(f"{n}={f}" for n, f in decisions))
    for q in queries:
        print(f"query {q.app} strategy={q.strategy} priority={q.priority} "
              f"latency={q.latency:.4f}s fn_s={q.fn_s:.4f} "
              f"invocations={q.invocations}"
              + (f" error={q.error}" if q.error else ""))

    result = check(dep, queries, warmup, cell.limits)
    metrics = read_all(cell.per_layer if args.trace else cell.end_to_end,
                       view)
    attempted = len(queries)
    correct = attempted > 0 and all(
        c["value"] <= c["limit"] for c in result["checks"].values())
    for e in result["errors"]:
        print(f"query error: {e}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": correct, "attempted": attempted,
            "failed": result["failed"], "metrics": metrics, "device": device,
            **extra, "checks": result["checks"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
