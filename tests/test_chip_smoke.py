"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The script itself runs only on a TPU. Here its phase functions are loaded
by path and steered from the test: the kernel phase forces the Pallas
kernels in interpret mode, and the query phase has the dispatch layer take
the Pallas path (interpret mode off-TPU) for every shuffle kernel, so the
same checks the chip run makes — references, oracle tolerance, ``pallas``
dispatch counts — run in CI.
"""

import importlib.util
from pathlib import Path

import pytest

from repro.analytics.query import ORACLE_RTOL
from repro.kernels import ops as kops

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_kernel_phase_matches_refs_in_interpret_mode(smoke):
    lines = smoke.kernel_phase(ids=1 << 12, probe_rows=1 << 10,
                               force_kernel=True)
    assert [line.split()[0] for line in lines] == \
        ["histogram", "histogram", "grouping", "scatter", "fused"]


@pytest.mark.parametrize("zipf", [0.0, 1.5])
def test_query_phase_takes_pallas_paths_and_meets_oracle(smoke, monkeypatch,
                                                        zipf):
    monkeypatch.setattr(kops, "_kernel_path", lambda force_kernel: "pallas")
    r = smoke.query_phase(rows=1 << 12, dim_rows=1 << 8, keyspace=1 << 9,
                          fact_nodes=4, dim_nodes=2, num_groups=64, seed=3,
                          zipf=zipf)
    assert r["rel_err"] <= ORACLE_RTOL
    smoke._require_pallas(r, ("kernel/grouping", "kernel/histogram"))
    assert not any(path == "jit" for _, path in r["paths"])
    assert dict(r["decisions"])["exchange"] == "shuffle"


def test_main_refuses_a_cpu_platform(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform='cpu'" not in out and "'cpu'" in err
    assert '"ok"' not in out
