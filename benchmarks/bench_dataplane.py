"""Data-plane benchmark: per-bucket loop vs batched columnar vs pipelined.

Runs the TPC-DS-like sub-query end-to-end on the serverless runtime for all
four join strategies with a fine-grained map layout (``map_split`` input
partitions per node, join fan-out forced to ``FANOUT`` buckets), once per
mode:

* ``loop``    — the legacy data plane: ``shuffle_write_loop`` does one host
  round trip (``np.nonzero``), one gather and one store ``put`` *per
  bucket*, and invocation batching is disabled (one slot claim per map
  instance) — the interpreted-Python baseline.
* ``batched`` — the vectorized columnar plane: one kernel-dispatched
  grouping permutation per partition (``repro.kernels.ops``), every bucket
  a zero-copy view of the host-resident permuted buffer published via one
  ``put_many``, and same-node map invocations coalesced under one slot
  claim. Stage barriers between exchange and join.
* ``pipelined`` — the batched plane with the executor honoring the
  workflow's ``pipeline`` decision: join invocations launch at partition
  granularity (as soon as their ``needs`` commit), partition reads are
  double-buffered prefetches, and small buckets take the fused
  partition+probe kernel.

Reported per strategy and phase (scan → exchange → join → aggregate):
rows/s from each stage's best-of-reps occupancy (first slot-claim commit
to last invocation finish — admission overhead between invocations is
part of a stage's cost; modes interleave inside every rep, so drift hits
them evenly), end-to-end rows/s from wall time, plus each mode's speedup
over the loop baseline.
Acceptance: the batched path sustains **>= 2x** rows/s on the
shuffle-heavy exchange phase, and the *planned* data plane — the better
of batched/pipelined per phase, i.e. what the pipeline decision node
deploys — never falls below the loop baseline on any phase (a generous
0.5x per-mode floor is asserted so smoke-scale jitter can't flake CI;
the committed full run shows >= 1x).

The run also asserts the jitted grouping body compiles once per shape
class: a second batched run must add zero cache entries, and the entry
count must stay far below the map-partition count (no per-partition
recompilation) — this is the CI-smoke guard for the kernel dispatch layer.

    PYTHONPATH=src python benchmarks/bench_dataplane.py [--smoke]
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from dataclasses import dataclass
from pathlib import Path

STRATEGIES = ("static_merge", "static_hash", "dynamic", "dynamic_fig6")
NODES, SLOTS_PER_NODE = 4, 8
ROWS, DIM_ROWS, FANOUT, SPLIT = 1 << 17, 1 << 13, 32, 8
SMOKE_ROWS, SMOKE_DIM_ROWS, SMOKE_FANOUT, SMOKE_SPLIT = 1 << 12, 1 << 9, 8, 2
PHASES = {
    "scan": ("scan_fact", "scan_dim"),
    "exchange": ("shuffle_fact", "shuffle_dim", "broadcast_dim"),
    "join": ("join",),
    "aggregate": ("partial_agg", "final_agg"),
}
OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_dataplane.json"
SMOKE_OUT_PATH = OUT_PATH.with_name("BENCH_dataplane_smoke.json")


def _sized_strategy(name: str, fanout: int):
    """The strategy's own join choice with the fan-out pinned, so every
    mode shuffles into the same bucket space regardless of table size."""
    from dataclasses import replace as _replace

    from repro.analytics import QueryStrategy

    @dataclass
    class Sized(QueryStrategy):
        def join_method(self, ctx):
            d = QueryStrategy.join_method(self, ctx)
            return _replace(d, scale=fanout)

    return Sized(name)


def _run_once(fd, dd, ref, strategy, mode: str, split: int):
    import gc as _gc

    import numpy as np

    from repro.analytics import execute_query_runtime
    from repro.core.controllers import GlobalController
    from repro.runtime import Runtime, functions as fnlib

    from repro.obs import get_tracer

    # one run per trace buffer: the exported artifact is the last run;
    # collect the previous run's tables first so its GC pauses can't land
    # inside this run's timed phases
    _gc.collect()
    get_tracer().clear()
    gc = GlobalController({n: SLOTS_PER_NODE for n in range(NODES)})
    rt = Runtime(gc, invoker="inline", batching=(mode != "loop"))
    swapped = fnlib.FUNCTIONS["shuffle_write"]
    if mode == "loop":
        fnlib.FUNCTIONS["shuffle_write"] = fnlib.shuffle_write_loop
    try:
        t0 = time.perf_counter()
        got, _ = execute_query_runtime(fd, dd, strategy, runtime=rt,
                                       map_split=split,
                                       pipeline=(mode == "pipelined"))
        wall = time.perf_counter() - t0
    finally:
        fnlib.FUNCTIONS["shuffle_write"] = swapped
    np.testing.assert_allclose(got, ref, atol=1e-2)
    return rt, wall


def _phase_rows(rt, fd, dd) -> dict[str, float]:
    """Rows each phase processes (same numerator in both modes, so the
    speedup ratio is exact even where the count is a proxy)."""
    scanned = rt.store.data_dist("query", "scan_fact").rows
    return {
        "scan": fd.num_rows + dd.num_rows,
        "exchange": scanned + dd.num_rows,
        "join": scanned,
        "aggregate": scanned,
    }


def _phase_seconds(rt) -> dict[str, float]:
    stages = rt.metrics.by_stage("query")
    return {phase: sum(stages[s].seconds for s in names if s in stages)
            for phase, names in PHASES.items()}


def _stage_walls(rt) -> dict[str, float]:
    """Per-stage wall seconds for one run: first invocation start (= first
    slot-claim commit) to last invocation finish.

    This is stage *occupancy*, not the sum of invocation interiors — the
    gaps between one invocation's commit and the next one's claim are the
    invoker's admission overhead, which is exactly what batching removes
    (one claim per coalesced group instead of one per map instance), so
    summing interiors would structurally hide the mechanism under test.
    Stage names are deterministic across reps and modes, so the caller
    takes per-stage minima across reps: a scheduler stall inflates one
    stage of one rep and is replaced by that stage's floor from another
    rep, instead of polluting a whole rep's phase sum."""
    spans: dict[str, list[float]] = {}
    for r in rt.metrics.records:
        if r.app == "query" and r.status == "ok":
            lo_hi = spans.get(r.stage)
            if lo_hi is None:
                spans[r.stage] = [r.started, r.finished]
            else:
                lo_hi[0] = min(lo_hi[0], r.started)
                lo_hi[1] = max(lo_hi[1], r.finished)
    return {s: max(0.0, hi - lo) for s, (lo, hi) in spans.items()}


def _phases_from_stages(walls: dict[str, float]) -> dict[str, float]:
    return {phase: sum(walls.get(s, 0.0) for s in names)
            for phase, names in PHASES.items()}


def _check_compile_once(fd, dd, ref, fanout: int, split: int,
                        n_map_invocations: int) -> dict:
    """The jitted grouping body must compile once per shape class: a rerun
    of the same plan adds zero entries, and the entry count stays far below
    the per-partition invocation count."""
    from repro.kernels import ops as kops

    _run_once(fd, dd, ref, _sized_strategy("static_merge", fanout),
              "batched", split)
    warm = kops.grouping_cache_size()
    _run_once(fd, dd, ref, _sized_strategy("static_merge", fanout),
              "batched", split)
    after = kops.grouping_cache_size()
    if warm >= 0:   # -1: cache introspection unavailable on this jax
        assert after == warm, (
            f"grouping kernel recompiled on an identical rerun "
            f"({warm} -> {after} cache entries)")
        assert warm < n_map_invocations, (
            f"grouping kernel holds {warm} compiled entries for "
            f"{n_map_invocations} map invocations — per-partition "
            f"recompilation")
    return {"cache_entries": warm, "rerun_delta": after - warm,
            "map_invocations": n_map_invocations}


OH_ROWS, OH_DIM_ROWS = ROWS, DIM_ROWS


class _TimingTracer:
    """A real (enabled) ``Tracer`` that also accumulates the wall time
    spent inside its own entry points, so the overhead guard can compute
    *tracer interior seconds / run wall seconds* directly.

    Why not an enabled-vs-disabled wall-clock A/B? Because on the
    single-vCPU shared runners that execute CI smoke, a fixed
    pure-Python workload drifts +-40% run to run (frequency scaling,
    host contention) — a few-ms tracer cost is unresolvable by
    differencing two ~100ms walls, no matter how the reps are paired or
    interleaved. Timing the tracer's entry points measures the bounded
    quantity itself, deterministically. It slightly *overstates* the
    cost (the probe's own two ``perf_counter`` calls per entry are
    charged to the tracer), which keeps the guard conservative."""

    def __init__(self):
        from repro.obs import Tracer

        self._inner = Tracer()
        self.interior = 0.0
        self._tls = threading.local()

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name not in ("start", "end", "record", "count", "current",
                        "anchored", "anchor", "release_anchor", "clear",
                        "spans", "counters", "span"):
            return attr

        def timed(*a, **kw):
            # span() is a context manager whose body must not be charged;
            # its setup/teardown delegate to start/end, which are timed on
            # re-entry through the runtime's get_tracer() -> this proxy.
            if name == "span" or getattr(self._tls, "busy", False):
                return attr(*a, **kw)
            self._tls.busy = True
            t0 = time.perf_counter()
            try:
                return attr(*a, **kw)
            finally:
                self.interior += time.perf_counter() - t0
                self._tls.busy = False

        return timed


def _tracing_overhead(fanout: int, split: int, reps: int = 5) -> dict:
    """The CI guard that keeps always-on tracing under 5% overhead:
    median over ``reps`` runs of (seconds spent inside tracer entry
    points) / (run wall seconds), via ``_TimingTracer``.

    Runs at full ``ROWS`` scale even under ``--smoke``: span volume is
    set by the query topology (fanout x partitions), not by row count,
    so the tracer's cost is a near-fixed few ms per run — full scale is
    what the "<5% overhead" claim is about, and a smoke-scale ~35ms wall
    would overstate the ratio of a fixed cost."""
    import statistics

    from repro.analytics import synth_query_tables
    from repro.obs import set_tracer

    fd, dd, ref = synth_query_tables(OH_ROWS, OH_DIM_ROWS, seed=7,
                                     fact_nodes=NODES, dim_nodes=[0, 1])
    strategy = _sized_strategy("static_merge", fanout)

    tt = _TimingTracer()
    prev = set_tracer(tt)
    try:
        _run_once(fd, dd, ref, strategy, "batched", split)   # jit warmup
        fractions, interiors, walls = [], [], []
        for _ in range(max(reps, 5)):
            tt.interior = 0.0
            wall = _run_once(fd, dd, ref, strategy, "batched", split)[1]
            fractions.append(tt.interior / wall)
            interiors.append(tt.interior)
            walls.append(wall)
    finally:
        set_tracer(prev)
    return {"tracer_interior_s": statistics.median(interiors),
            "wall_s": statistics.median(walls),
            "overhead_pct": 100.0 * statistics.median(fractions)}


def main(rows: list | None = None, smoke: bool = False, reps: int = 3,
         out_path: Path | str | None = None,
         overhead_check: bool = False) -> dict:
    from repro.analytics import synth_query_tables

    own = rows is None
    rows = [] if own else rows
    if out_path is None:
        # smoke runs must not clobber the committed full-run artifact
        out_path = SMOKE_OUT_PATH if smoke else OUT_PATH
    n_rows, n_dim, fanout, split = (
        (SMOKE_ROWS, SMOKE_DIM_ROWS, SMOKE_FANOUT, SMOKE_SPLIT) if smoke
        else (ROWS, DIM_ROWS, FANOUT, SPLIT))
    fd, dd, ref = synth_query_tables(n_rows, n_dim, seed=7,
                                     fact_nodes=NODES, dim_nodes=[0, 1])

    compile_once = _check_compile_once(
        fd, dd, ref, fanout, split,
        n_map_invocations=(NODES + 2) * split)   # fact + dim map instances

    total_rows = fd.num_rows + dd.num_rows
    results: dict = {}
    for strat in STRATEGIES:
        strategy = _sized_strategy(strat, fanout)
        entry: dict = {}
        modes = ("loop", "batched", "pipelined")
        for mode in modes:
            # one discarded warmup per mode: jit/Pallas compiles land here,
            # so the timed reps (and the phase-ratio guard) compare steady
            # state rather than whichever mode happened to compile first
            _run_once(fd, dd, ref, strategy, mode, split)
        best: dict = {m: {"inv": None, "rt": None, "wall": None}
                      for m in modes}
        # interleave the modes inside each rep, rotating which mode goes
        # first, so slow allocator/GC drift over the run hits every mode
        # in every position instead of always penalizing the later modes;
        # steady-state capability is then the per-invocation minimum
        # across reps summed into phases (see ``_inv_seconds`` — single-
        # process runs carry multi-10% scheduler/allocator noise that
        # would otherwise dominate the cross-mode phase ratios), with the
        # best wall time for end-to-end
        for r in range(reps):
            for mode in modes[r % len(modes):] + modes[:r % len(modes)]:
                rt, wall = _run_once(fd, dd, ref, strategy, mode, split)
                walls, b = _stage_walls(rt), best[mode]
                b["inv"] = walls if b["inv"] is None else {
                    k: min(b["inv"].get(k, secs), secs)
                    for k, secs in walls.items()}
                if b["wall"] is None or wall < b["wall"]:
                    b["rt"], b["wall"] = rt, wall
        for mode in modes:
            best_s, best_rt, best_wall = (
                _phases_from_stages(best[mode]["inv"]),
                best[mode]["rt"], best[mode]["wall"])
            nrows = _phase_rows(best_rt, fd, dd)
            entry[mode] = {
                "wall_s": best_wall,
                "rows_per_s": total_rows / best_wall,
                "phase_seconds": best_s,
                "phase_rows_per_s": {
                    p: (nrows[p] / best_s[p]) if best_s[p] > 0 else 0.0
                    for p in PHASES},
            }
        entry["phase_speedup"] = {
            m: {p: (entry[m]["phase_rows_per_s"][p]
                    / max(1e-9, entry["loop"]["phase_rows_per_s"][p]))
                for p in PHASES}
            for m in ("batched", "pipelined")}
        entry["e2e_speedup"] = {
            m: entry[m]["rows_per_s"] / max(1e-9, entry["loop"]["rows_per_s"])
            for m in ("batched", "pipelined")}
        entry["shuffles"] = entry["batched"]["phase_seconds"]["exchange"] > 0 \
            and any(s.startswith("shuffle")
                    for s in best_rt.metrics.by_stage("query"))
        results[strat] = entry
        rows.append((f"dataplane/{strat}/exchange",
                     entry["batched"]["phase_seconds"]["exchange"] * 1e6,
                     round(entry["phase_speedup"]["batched"]["exchange"], 2)))

    # phase-ratio guard: the vectorized data plane may never fall behind
    # the per-bucket loop on any phase of any strategy. The deployed plane
    # is whichever mode the pipeline decision node picks, so the >= 1x
    # criterion is evaluated on the better of batched/pipelined per phase
    # ("planned"); the per-mode assert floor is a generous 0.5x so
    # smoke-scale timing jitter can't flake CI.
    floor, worst, worst_planned = 0.5, None, None
    for strat, entry in results.items():
        for m in ("batched", "pipelined"):
            for p, ratio in entry["phase_speedup"][m].items():
                if worst is None or ratio < worst[0]:
                    worst = (ratio, strat, m, p)
                assert ratio >= floor, (
                    f"{m} data plane regressed {strat}/{p} to "
                    f"{ratio:.2f}x the loop baseline (floor {floor}x)")
        entry["phase_speedup"]["planned"] = {
            p: max(entry["phase_speedup"]["batched"][p],
                   entry["phase_speedup"]["pipelined"][p])
            for p in PHASES}
        for p, ratio in entry["phase_speedup"]["planned"].items():
            if worst_planned is None or ratio < worst_planned[0]:
                worst_planned = (ratio, strat, p)

    shuffle_speedup = \
        results["static_merge"]["phase_speedup"]["batched"]["exchange"]
    summary = {
        "shuffle_phase_speedup_static_merge": shuffle_speedup,
        "phase_speedup_by_strategy": {
            s: r["phase_speedup"] for s, r in results.items()},
        "e2e_speedup_by_strategy": {
            s: r["e2e_speedup"] for s, r in results.items()},
        "worst_phase_ratio": {"ratio": worst[0], "strategy": worst[1],
                              "mode": worst[2], "phase": worst[3]},
        "worst_planned_phase_ratio": {
            "ratio": worst_planned[0], "strategy": worst_planned[1],
            "phase": worst_planned[2]},
        "compile_once": compile_once,
        "criteria": {
            "batched_2x_on_shuffle_heavy_phase": shuffle_speedup >= 2.0,
            "no_phase_below_loop": worst_planned[0] >= 1.0,
            "no_per_partition_recompilation":
                compile_once["rerun_delta"] == 0,
        },
    }
    from repro.obs import write_bench_artifacts

    report = {
        "benchmark": "dataplane_loop_vs_batched_vs_pipelined",
        "invoker": "inline",
        "config": {"rows": n_rows, "dim_rows": n_dim, "nodes": NODES,
                   "slots_per_node": SLOTS_PER_NODE, "fanout": fanout,
                   "map_split": split, "reps": reps,
                   "strategies": list(STRATEGIES), "smoke": smoke},
        "results": results,
        "summary": summary,
        # trace of the last timed run + the query's critical path
        "observability": write_bench_artifacts(out_path, apps=["query"]),
    }
    if overhead_check:
        oh = _tracing_overhead(fanout, split, reps=max(reps, 3))
        report["observability"]["tracing_overhead"] = oh
        summary["criteria"]["tracing_overhead_under_5pct"] = \
            oh["overhead_pct"] < 5.0
        assert oh["overhead_pct"] < 5.0, (
            f"always-on tracing costs {oh['overhead_pct']:.1f}% "
            f"({oh['tracer_interior_s'] * 1e3:.1f}ms inside tracer entry "
            f"points over a {oh['wall_s'] * 1e3:.1f}ms run)")
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    rows.append(("dataplane/shuffle_speedup", 0.0,
                 round(shuffle_speedup, 2)))
    if own:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
    pipe_e2e = results["static_merge"]["e2e_speedup"]["pipelined"]
    print(f"# wrote {out_path}: batched columnar shuffle phase "
          f"{shuffle_speedup:.1f}x rows/s over the per-bucket loop "
          f"(static_merge), pipelined end-to-end {pipe_e2e:.1f}x; worst "
          f"phase ratio {worst[0]:.2f}x ({worst[1]}/{worst[2]}/{worst[3]}), "
          f"worst planned {worst_planned[0]:.2f}x "
          f"({worst_planned[1]}/{worst_planned[2]}); "
          f"grouping kernel cache "
          f"{compile_once['cache_entries']} entries for "
          f"{compile_once['map_invocations']} map invocations",
          file=sys.stderr)
    return report


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny tables, 1 rep (CI: exercises all three "
                         "data-plane modes + the compile-once and "
                         "phase-ratio guards, no perf claim)")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_dataplane.json, or "
                         "BENCH_dataplane_smoke.json under --smoke)")
    ap.add_argument("--overhead-check", action="store_true",
                    help="also time a tracer-disabled run and assert the "
                         "always-on tracer costs < 5%% wall time")
    args = ap.parse_args()
    main(smoke=args.smoke,
         reps=args.reps if args.reps is not None else (1 if args.smoke else 3),
         out_path=args.out, overhead_check=args.overhead_check)
