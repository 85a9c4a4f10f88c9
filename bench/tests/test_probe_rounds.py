"""The hash probe's depth reading (``kern.hash_probe_rounds``): on
synthetic spans, on the spans the program's join records when traced, and
``None`` from a program that records no depth."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import bodyspans
from benchlib.readers import RunView, load_reader
from repro.analytics import operators
from repro.analytics.table import Table
from repro.obs import Span, Tracer, get_tracer, set_tracer

NAME = "kern.hash_probe_rounds"


def _join_span(sid, method, **attrs):
    return Span(sid, "q0", "kernel/join", "kernel", 0.0, end=1.0,
                attrs={"method": method, "path": "jit", **attrs})


@pytest.fixture
def tracer():
    tr = Tracer(enabled=True)
    old = set_tracer(tr)
    yield tr
    set_tracer(old)


def _read(monkeypatch, spans):
    monkeypatch.setattr(bodyspans, "last_unit_spans", lambda: spans)
    return load_reader(NAME)(RunView([], 1.0, 1.0))


def test_mean_over_the_hash_joins_only(monkeypatch):
    spans = [_join_span(1, "hash", probe_rounds=1, max_probes=16),
             _join_span(2, "hash", probe_rounds=4, max_probes=16),
             _join_span(3, "merge"),
             Span(4, "q0", "kernel/fused_probe", "kernel", 0.0, end=1.0,
                  attrs={"probe_rounds": 9})]
    assert _read(monkeypatch, spans) == pytest.approx(2.5)


@pytest.mark.parametrize("spans", [
    [],
    [_join_span(1, "merge")],
    [_join_span(1, "hash")],            # a program that records no depth
], ids=["no_spans", "merge_only", "hash_without_depth"])
def test_nothing_to_read_is_none(monkeypatch, spans):
    assert _read(monkeypatch, spans) is None


def _tables(build_keys):
    n = len(build_keys)
    probe = Table({"key": jnp.arange(2 * n, dtype=jnp.int32)})
    build = Table({"key": jnp.asarray(build_keys, jnp.int32),
                   "cat": jnp.arange(n, dtype=jnp.int32)})
    return probe, build


def test_the_traced_join_records_its_depth(tracer, monkeypatch):
    """Dense item keys: the program's join span says one round of 16."""
    probe, build = _tables(np.arange(512))
    with tracer.span("kernel/join", "kernel", method="hash", path="jit"):
        out = operators.join(probe, build, method="hash")
    assert np.asarray(out["found"]).sum() == 512
    (span,) = [s for s in tracer.spans() if s.name == "kernel/join"]
    assert span.attrs["probe_rounds"] == 1
    assert span.attrs["max_probes"] == 16
    # the depth came down through the traced copy, inside the join's span
    sync = [s for s in tracer.spans() if s.name == "sync/probe_rounds"]
    assert [s.parent_id for s in sync] == [span.span_id]
    assert _read(monkeypatch, tracer.spans()) == 1.0


def test_untraced_join_reads_nothing(monkeypatch):
    old = set_tracer(Tracer(enabled=False))
    try:
        monkeypatch.setattr(operators, "host_copy", _no_read)
        probe, build = _tables(np.arange(64))
        with get_tracer().span("kernel/join", "kernel", method="hash"):
            out = operators.join(probe, build, method="hash")
        assert np.asarray(out["found"]).sum() == 64
    finally:
        set_tracer(old)


def _no_read(*args, **kwargs):
    raise AssertionError("the probe depth was read with the tracer off")


def test_sh_cell_reads_the_depth_where_it_binds_the_hash_join(capsys,
                                                              monkeypatch):
    """The sh cell's traced run on the CPU, its fact table just large
    enough (3M rows: 4.5 MB buckets, over the fused probe's 4 MiB) that
    the plan binds the hash join as at SF10: the line carries every
    per-layer metric the cell lists but the device trace's, the probe
    depth among them, one round over the dense item keys."""
    import json

    import repro.compile_cache
    import run
    from benchlib.cell import load_cell

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off in tests")
    cell = load_cell("sf10.uniform.sh")
    cell.config = {**cell.config, "fact_rows": 3_000_000, "dim_rows": 512}
    rc = run.main(["--workload", cell.name, "--seed", str(2**31 + 7),
                   "--seconds", "0.2", "--trace", "1"],
                  cell=cell, require_chip=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer
                                    if m["source"] != "device_trace"}
    assert line["metrics"][NAME]["value"] == 1.0
