"""Finding a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root lists the cells. A cell names its
configuration and its traffic mix; each lives in a file of its own:

* ``bench/configs/<config>.json``  — the deployment: table sizes, nodes,
  slots, invoker, the guarantee (the path is the configuration's ``file``);
* ``bench/traffic/<traffic>.json`` — the mix: key law, strategies, loop
  kind, tenants' priorities (read by the one generator in ``drive.py``);
* ``bench/workloads/<cell>.json``  — the cell's correctness limit;
* ``bench/metrics/<metric>.py``    — one reader per metric (``readers.py``).

Adding a cell, a mix or a metric adds files and entries; no existing file
changes.
"""

from __future__ import annotations

import json
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
