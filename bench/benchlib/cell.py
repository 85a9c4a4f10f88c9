"""Finding a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root lists the cells. A cell names its
configuration and its traffic mix; each lives in a file of its own:

* ``bench/configs/<config>.json``  — the deployment: table sizes, nodes,
  slots, invoker, the guarantee (the path is the configuration's ``file``);
  its ``app`` key names the application (default ``tpcds_join_agg``);
* ``bench/apps/<app>.py``          — the application: its tables, the query
  each loop submits, its reference, control and error (``drive.py`` says
  what an app module provides);
* ``bench/traffic/<traffic>.json`` — the mix: key law, strategies, loop
  kind, tenants' priorities (read by the one generator in ``drive.py``);
* ``bench/loops/<loop>.py``        — the loop a traffic names: how a unit's
  queries are submitted and stamped;
* ``bench/workloads/<cell>.json``  — the cell's correctness limit;
* ``bench/metrics/<metric>.py``    — one reader per metric (``readers.py``).

Adding a cell, a mix, an application, a loop or a metric adds files and
entries; no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded by its path."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (BENCH_DIR / "workloads" / f"{name}.json").read_text())["limits"]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e, layer)
