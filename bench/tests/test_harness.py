"""The harness end to end at a tiny size on the CPU: each cell's traffic
with the Pallas kernels in interpret mode, the refusal of a platform
without a chip, faults planted under the timed path (each must make
``correct`` false), and the bfloat16 control against the cell's limit.

A cell's sizes are in ``tiny/<config>.json``, or in ``tiny/<cell>.json``
where the cell needs its own: ``sf10.uniform.sh`` is sized so that its
join buckets exceed the fused probe's ``FUSED_BUCKET_BYTES`` and the plan
binds the hash join, as at SF10."""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import run
from benchlib import drive
from benchlib.cell import load_cell
from repro.analytics.table import Table
from repro.kernels import ops as kops
from repro.runtime.functions import FUNCTIONS

WORKLOADS = {w["name"]: w for w in json.loads(
    (run.BENCH_DIR.parent / "BENCHMARK.json").read_text())["workloads"]}
CELLS = list(WORKLOADS)
TINY = Path(__file__).resolve().parent / "tiny"
SEED = 2**31 + 12_345


def tiny(name):
    """The cell with the sizes of ``tiny/<cell>.json`` where the cell has
    one, else of ``tiny/<config>.json``."""
    cell = load_cell(name)
    path = TINY / f"{name}.json"
    if not path.is_file():
        path = TINY / f"{WORKLOADS[name]['config']}.json"
    cell.config = {**cell.config, **json.loads(path.read_text())}
    return cell


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off in tests")


def _run(capsys, cell, trace=0, seconds=0.2):
    rc = run.main(["--workload", cell.name, "--seed", str(SEED),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  cell=cell, require_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_runs_with_interpret_mode_kernels(name, capsys,
                                                    monkeypatch):
    monkeypatch.setattr(kops, "_kernel_path", lambda force_kernel: "pallas")
    line = _run(capsys, tiny(name), trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert line["checks"]["rel_err_max"]["value"] <= \
        line["checks"]["rel_err_max"]["limit"]
    # a CPU run has no device trace; every other per-layer metric reads
    assert set(line["metrics"]) == {m["name"] for m in load_cell(name)
                                    .per_layer
                                    if m["source"] != "device_trace"}


@pytest.mark.parametrize("name", CELLS)
def test_end_to_end_metrics_of_each_cell(name, capsys):
    line = _run(capsys, tiny(name))
    assert line["correct"] is True
    assert set(line["metrics"]) == {m["name"] for m in load_cell(name)
                                    .end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_refuses_a_platform_without_a_chip(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == run.EXIT_NO_CHIP
    assert "TPU" in err
    assert not any(l.startswith("{") for l in out.splitlines())


def _wrap_put(name, alter, monkeypatch, method="put"):
    original = FUNCTIONS[name]

    def broken(ctx):
        put = getattr(ctx, method)
        setattr(ctx, method, lambda *a: put(*alter(*a)))
        original(ctx)

    monkeypatch.setitem(FUNCTIONS, name, broken)


def _alter_answer(stage, part, table):
    s = np.asarray(table["sum"]).copy()
    s[np.argmax(np.abs(s))] *= 1.01
    return stage, part, Table({"sum": jnp.asarray(s)})


def _drop_half(stage, part, table):
    return stage, part, table.take(jnp.arange(0, table.num_rows, 2))


def _drop_bucket(stage, tables):
    return stage, {p: t for p, t in tables.items() if p != min(tables)}


FAULTS = {
    "answer_altered": ("final_aggregate", _alter_answer, "put"),
    "half_the_rows_left_out": ("scan_filter", _drop_half, "put"),
    "exchange_left_out": ("shuffle_write", _drop_bucket, "put_many"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_fault_under_the_timed_path_is_not_correct(name, fault, capsys,
                                                     monkeypatch):
    cell = tiny(name)
    func, alter, method = FAULTS[fault]
    probe = drive.Deployment(cell.config, cell.traffic, SEED)
    plan = {f for q in probe.run_unit(1) for _, f in q.decisions}
    if fault == "exchange_left_out" and "shuffle" not in plan:
        pytest.skip(f"{name} has no exchange to leave out")
    _wrap_put(func, alter, monkeypatch, method)
    line = _run(capsys, cell)
    assert line["correct"] is False
    assert line["failed"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes_the_limit(name):
    cell = tiny(name)
    dep = drive.Deployment(cell.config, cell.traffic, SEED)
    r = calibrate.readings(dep, 1)
    limit = cell.limits["rel_err"]
    assert r["program"] <= limit < r["control"]


# -- a deployment that brings its own app and loop as new files ---------------

TOY_APP = '''
"""A keyed SUM over seeded (group, weight) partitions: one
partial_aggregate per partition, then final_aggregate, on the runtime."""

import numpy as np

from benchlib import gen


def make_tenants(config, traffic, seed):
    import jax

    parts, rows = int(config["partitions"]), int(config["rows"])
    tenants = []
    for i in range(int(config["tenants"])):
        k1, k2 = jax.random.split(gen.prng_key(seed + i))
        group = jax.random.randint(k1, (parts, rows), 0,
                                   int(config["num_groups"]))
        weight = jax.random.uniform(k2, (parts, rows))
        tenants.append([{"group": group[p], "weight": weight[p]}
                        for p in range(parts)])
    jax.block_until_ready(tenants)
    return tenants


def input_rows(config):
    return int(config["partitions"]) * int(config["rows"])


class closed:
    def __init__(self, dep, tenant, app, strategy, priority):
        self.dep, self.tenant, self.app = dep, tenant, app
        self.priority = priority

    def run(self):
        from repro.analytics.table import Table
        from repro.runtime import Invocation, RuntimeStage

        rt, app, g = self.dep.runtime, self.app, self.dep.config["num_groups"]
        nodes = int(self.dep.config["nodes"])
        rt.seed(app, "input", {p: Table(dict(cols))
                               for p, cols in enumerate(self.tenant)})
        partial = [Invocation(f"{app}/partial/{p}", app, "partial", p,
                              "partial_aggregate", p % nodes, self.priority,
                              params={"src": "input", "dst": "partial",
                                      "partition": p, "num_groups": g})
                   for p in range(len(self.tenant))]
        final = [Invocation(f"{app}/final/0", app, "final", 0,
                            "final_aggregate", 0, self.priority,
                            params={"src": "partial", "dst": "result",
                                    "num_groups": g})]
        rt.execute([RuntimeStage("partial", partial),
                    RuntimeStage("final", final, deps=("partial",))])
        return np.asarray(rt.result(app), np.float64)

    def decisions(self):
        return ()


def _host(tenant, dtype):
    group = np.concatenate([np.asarray(p["group"]) for p in tenant])
    weight = np.concatenate([np.asarray(p["weight"]) for p in tenant])
    return group, weight.astype(dtype).astype(np.float64)


def reference(config, tenant):
    group, weight = _host(tenant, np.float64)
    return np.bincount(group, weights=weight,
                       minlength=int(config["num_groups"]))


def control(config, tenant):
    import ml_dtypes

    group, weight = _host(tenant, ml_dtypes.bfloat16)
    return np.bincount(group, weights=weight,
                       minlength=int(config["num_groups"]))


def error(got, ref):
    if got is None or np.shape(got) != np.shape(ref):
        return float("inf")
    return float(np.abs(got - ref).max() / np.abs(ref).max())
'''

TOY_LOOP = '''
"""Each tenant's query in turn, one client."""

import time

from benchlib.drive import QueryRec


def run_unit(dep, unit):
    recs = []
    for i, t in enumerate(dep.tenants):
        app = f"e{unit}t{i}"
        query = dep.app.closed(dep, t, app, "none", 0)
        rec = QueryRec(unit, app, i, "none", 0, time.perf_counter(),
                       fact_rows=dep.input_rows)
        rec.answer = query.run()
        rec.done = time.perf_counter()
        done = dep.runtime.metrics.for_app(app)
        rec.fn_s = sum(r.seconds for r in done)
        rec.invocations = len(done)
        dep.runtime.release(app)
        dep.runtime.metrics.clear(app)
        recs.append(rec)
    return recs
'''

TOY_CONFIG = {"app": "toy_keyed_sum", "source": "a test's toy deployment",
              "tenants": 2, "partitions": 4, "rows": 2048, "num_groups": 8,
              "nodes": 2, "slots_per_node": 4, "invoker": "threads",
              "max_workers": 4}


@pytest.fixture
def toy_bench(tmp_path, monkeypatch):
    """A copy of ``bench/`` with a toy app, loop, config, traffic and
    workload added as files, and ``BENCHMARK.json`` entries naming them."""
    import shutil

    from benchlib import cell as cell_mod

    bench = tmp_path / "bench"
    shutil.copytree(run.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench / "apps" / "toy_keyed_sum.py").write_text(TOY_APP)
    (bench / "loops" / "each_tenant.py").write_text(TOY_LOOP)
    (bench / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    (bench / "traffic" / "each.json").write_text(
        json.dumps({"loop": "each_tenant"}))
    (bench / "workloads" / "toy.each.json").write_text(
        json.dumps({"limits": {"rel_err": 1e-5}}))
    spec = json.loads((run.BENCH_DIR.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "a test",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "a test"})
    spec["workloads"].append({"name": "toy.each", "config": "toy",
                              "traffic": "each", "chips": 1, "why": "a test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("rows_per_s", "fn_s_per_query",
                         "inv.invocations_per_query"):
            m["workloads"].append("toy.each")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(cell_mod, "BENCH_DIR", bench)
    return load_cell("toy.each", root=tmp_path)


@pytest.mark.parametrize("planted", [False, True],
                         ids=["as_run", "answer_altered"])
def test_an_app_and_a_loop_added_as_files_run(toy_bench, planted, capsys,
                                              monkeypatch):
    if planted:
        _wrap_put("final_aggregate", _alter_answer, monkeypatch)
    for trace, metrics in ((0, {"rows_per_s", "fn_s_per_query", "setup_s"}),
                           (1, {"inv.invocations_per_query"})):
        line = _run(capsys, toy_bench, trace=trace)
        assert line["correct"] is not planted
        assert line["attempted"] >= 2 and line["failed"] == (
            line["attempted"] if planted else 0)
        assert set(line["metrics"]) == metrics


def test_calibration_reads_the_toy_apps_control(toy_bench):
    dep = drive.Deployment(toy_bench.config, toy_bench.traffic, SEED)
    r = calibrate.readings(dep, 1)
    assert r["program"] <= toy_bench.limits["rel_err"] < r["control"]
