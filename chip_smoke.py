"""Drive the analytics query path once on one TPU chip and check every answer.

    python chip_smoke.py [--seed N]

Runs only where JAX finds a TPU: on any other platform it names the
platform it found and exits nonzero, with no CPU fallback. One process owns
the chip; the query runs on the ``threads`` invoker, so no worker process
ever asks for it. Phases, in this order, none caught:

  (a) device  — platform, device kind and device count.
  (b) kernels — the Pallas partition kernels at real widths (histogram at
      2^23 ids with 32 and 512 buckets, grouping and scatter at 2^23 ids,
      fused probe at 2^16 probe x 4096 build rows), each checked against
      ``repro.kernels.ref``: bit for bit for ids, counts and permutations,
      allclose for probe weights.
  (c) query   — the TPC-DS-like sub-query end to end through
      ``execute_query_runtime`` (planner -> decision workflow -> DAG
      executor -> invoker -> shuffle store -> kernels) on a 2^25-row fact
      table (12 B rows, ~400 MB on the device) and a 2^20-row dim table,
      once with uniform keys and once with Zipf(1.5) keys, each checked
      against the float64 numpy oracle. The strategy is the paper's static
      merge join (S-M): at 400 MB on four fact nodes the Fig. 6 strategy
      consolidates onto one node and hash-joins, which shuffles nothing and
      so would run none of the partition kernels. The skewed run must bind
      the skew node's ``broadcast`` mitigation (hot-key build + probe).

Tolerance: a run's group sums may differ from the oracle by at most
``ORACLE_RTOL`` (1e-3) times the largest oracle group sum — the device sums
float32 per join partition (see ``repro.analytics.query``).

Wall seconds printed here are smoke timings of one cold run, compilation
included — not a benchmark. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

KERNEL_IDS = 1 << 23             # histogram and grouping/scatter ids
HIST_BUCKETS = (32, 512)
GROUP_BUCKETS = 32               # + the sentinel bucket = 33 kernel buckets
PROBE_ROWS = 1 << 16
QUERY = {"rows": 1 << 25, "dim_rows": 1 << 20, "keyspace": 1 << 21,
         "fact_nodes": 4, "dim_nodes": 2, "num_groups": 64}
ZIPF = 1.5
STRATEGY = "static_merge"


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _same(what: str, got, want) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        raise AssertionError(f"{what}: differs from the reference "
                             f"({bad} entries, shapes {got.shape} "
                             f"vs {want.shape})")


def kernel_phase(ids: int = KERNEL_IDS, probe_rows: int = PROBE_ROWS,
                 seed: int = 0, force_kernel: bool = False) -> list[str]:
    """Run each query-path kernel once through ``repro.kernels.ops`` and
    compare with ``repro.kernels.ref``. Returns one line per check; raises
    ``AssertionError`` on the first mismatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.kernels import ref

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    lines = []
    for i, buckets in enumerate(HIST_BUCKETS):
        pids = jax.random.randint(keys[i], (ids,), 0, buckets, jnp.int32)
        got = kops.partition_histogram(pids, buckets,
                                       force_kernel=force_kernel)
        _same(f"histogram {buckets}", got,
              ref.partition_histogram_ref(pids, buckets))
        lines.append(f"histogram   n={ids} buckets={buckets}: "
                     f"counts equal the reference")

    pids = jax.random.randint(keys[2], (ids,), 0, GROUP_BUCKETS, jnp.int32)
    order, offsets = kops.grouping_indices(pids, GROUP_BUCKETS,
                                           force_kernel=force_kernel)
    want_order, want_offsets = ref.partition_scatter_ref(
        jnp.arange(ids, dtype=jnp.int32), pids, GROUP_BUCKETS)
    _same("grouping order", order, want_order)
    _same("grouping offsets", offsets,
          np.append(np.asarray(want_offsets), ids))
    lines.append(f"grouping    n={ids} buckets={GROUP_BUCKETS}+1: "
                 f"permutation and offsets equal the reference")

    vals = jax.random.normal(keys[3], (ids,), jnp.float32)
    grouped, offsets = kops.partition_scatter(vals, pids, GROUP_BUCKETS,
                                              force_kernel=force_kernel)
    want_rows, want_offsets = ref.partition_scatter_ref(vals, pids,
                                                        GROUP_BUCKETS)
    _same("scatter rows", grouped, want_rows)
    _same("scatter offsets", offsets, want_offsets)
    lines.append(f"scatter     n={ids} buckets={GROUP_BUCKETS}: "
                 f"grouped rows and offsets equal the reference")

    m = kops.FUSED_VMEM_ROWS
    bk = jax.random.permutation(keys[4], 4 * m)[:m].astype(jnp.int32)
    bc = jax.random.randint(keys[5], (m,), 0, 1 << 20, jnp.int32)
    pk = jax.random.randint(keys[6], (probe_rows,), 0, 4 * m, jnp.int32)
    v0, v1 = jax.random.normal(keys[7], (2, probe_rows), jnp.float32)
    grp, wgt = kops.fused_probe_groups(pk, v0, v1, bk, bc, 64,
                                       force_kernel=force_kernel)
    want_grp, want_wgt = ref.fused_probe_ref(
        pk, v0, v1, bk, bc, jnp.ones((m,), jnp.int32), 64)
    _same("fused probe groups", grp, want_grp)
    np.testing.assert_allclose(wgt, np.asarray(want_wgt), rtol=1e-6,
                               err_msg="fused probe weights")
    matched = int(np.count_nonzero(np.asarray(want_wgt)))
    lines.append(f"fused probe n={probe_rows} build={m}: groups equal, "
                 f"weights allclose ({matched} matched rows)")
    return lines


def query_phase(rows: int, dim_rows: int, keyspace: int, fact_nodes: int,
                dim_nodes: int, num_groups: int, seed: int = 0,
                zipf: float = 0.0) -> dict:
    """One query through ``execute_query_runtime`` on the ``threads``
    invoker with the pipeline decision honored, checked against the numpy
    oracle. Raises ``AssertionError`` past ``ORACLE_RTOL``. Returns the
    bound decision sequence, ``kernel/*`` span counts by ``(name, path)``,
    the relative error, wall seconds and the runtime's per-stage table
    (invocations, function-seconds, store seconds, bytes)."""
    from repro.analytics import (QueryStrategy, build_query_workflow,
                                 execute_query_runtime, synth_query_tables)
    from repro.analytics.query import ORACLE_RTOL, oracle_relative_error
    from repro.obs import get_tracer

    tracer = get_tracer()
    tracer.clear()
    t0 = time.perf_counter()
    fact, dim, want = synth_query_tables(
        rows=rows, dim_rows=dim_rows, keyspace=keyspace, seed=seed,
        fact_nodes=fact_nodes, dim_nodes=dim_nodes, num_groups=num_groups,
        zipf=zipf)
    setup_s = time.perf_counter() - t0
    strategy = QueryStrategy(STRATEGY)
    workflow = build_query_workflow(strategy)
    t1 = time.perf_counter()
    got, runtime = execute_query_runtime(
        fact, dim, strategy, workflow=workflow, num_groups=num_groups,
        invoker="threads", pipeline=True)
    query_s = time.perf_counter() - t1
    err = oracle_relative_error(got, want)
    if not err <= ORACLE_RTOL:
        raise AssertionError(f"query (zipf={zipf}): relative error {err:.3e} "
                             f"exceeds {ORACLE_RTOL:g}")
    paths = Counter((s.name, s.attrs.get("path")) for s in tracer.spans()
                    if s.cat == "kernel")
    return {"decisions": tuple((n, d.func)
                               for n, d in workflow.last_run.sequence),
            "paths": dict(paths), "rel_err": err, "setup_s": setup_s,
            "query_s": query_s,
            "stages": runtime.metrics.format_table("query")}


def _require_pallas(result: dict, names: tuple[str, ...]) -> None:
    for name in names:
        if not result["paths"].get((name, "pallas")):
            raise AssertionError(f"no pallas dispatch of {name}: "
                                 f"{result['paths']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated table and kernel input")
    args = ap.parse_args(argv)

    info = device_info()
    print(f"(a) device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{info['platform']!r}", file=sys.stderr)
        return 1

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.analytics.query import ORACLE_RTOL

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    print("(b) kernels on the chip vs repro.kernels.ref", flush=True)
    for line in kernel_phase(seed=args.seed):
        print(f"  {line}", flush=True)
    print(f"  smoke timing (not a benchmark): kernel phase "
          f"{time.perf_counter() - t0:.3f} s wall, compiles included",
          flush=True)

    print(f"(c) query {QUERY}, strategy {STRATEGY}, invoker threads, "
          f"pipeline on; limit: relative error <= {ORACLE_RTOL:g}",
          flush=True)
    for zipf in (0.0, ZIPF):
        r = query_phase(seed=args.seed, zipf=zipf, **QUERY)
        print(f"  zipf={zipf}: relative error {r['rel_err']:.3e}", flush=True)
        print("    decisions: " + " ".join(f"{n}={f}"
                                           for n, f in r["decisions"]))
        print("    kernel spans by path: " + ", ".join(
            f"{n}[{p}]={c}" for (n, p), c in sorted(r["paths"].items())))
        print(f"    smoke timing (not a benchmark): tables "
              f"{r['setup_s']:.3f} s, query {r['query_s']:.3f} s wall, "
              f"compiles included")
        for row in r["stages"].splitlines():
            print(f"    {row}")
        sys.stdout.flush()
        _require_pallas(r, ("kernel/grouping", "kernel/histogram"))
        if zipf and ("skew", "broadcast") not in r["decisions"]:
            raise AssertionError(f"zipf={zipf}: the skew node did not bind "
                                 f"broadcast: {r['decisions']}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
