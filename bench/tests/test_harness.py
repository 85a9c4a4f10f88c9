"""The harness end to end at a tiny size on the CPU: each cell's traffic
with the Pallas kernels in interpret mode, the refusal of a platform
without a chip, faults planted under the timed path (each must make
``correct`` false), and the bfloat16 control against the cell's limit."""

import json

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

CELLS = [w["name"] for w in json.loads(
    (run.BENCH_DIR.parent / "BENCHMARK.json").read_text())["workloads"]]
TINY = {"tpcds_sf10_q42": {"fact_rows": 8_003, "dim_rows": 512},
        "tpcds_sf1_x8_tenants": {"fact_rows": 4_001, "dim_rows": 256}}
SEED = 2**31 + 12_345


def tiny(name):
    cell = load_cell(name)
    size = next(v for k, v in TINY.items()
                if cell.config["source"] == _source(k))
    cell.config = {**cell.config, **size}
    return cell


def _source(config_name):
    bench = json.loads((run.BENCH_DIR.parent / "BENCHMARK.json").read_text())
    file = next(c["file"] for c in bench["configs"]
                if c["name"] == config_name)
    return json.loads((run.BENCH_DIR.parent / file).read_text())["source"]


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
