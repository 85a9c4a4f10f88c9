"""Readings that set a cell's correctness limit (not part of a benchmark run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3,... [--units 1]

For each seed, in one process on the chip: the cell's tables made on the
device, ``--units`` units of the cell's traffic through the program (the
first seed's first unit compiles), and two readings of the number that
``bench/run.py`` compares, the app's error against its float64 reference
of each tenant's tables (``bench/apps/<app>.py``):

* ``program`` — the program's answers (a run's number is the largest
  over its queries: the lower reading of the limit is the largest of these
  over all seeds);
* ``control`` — the app's control, the reference one precision below the
  configuration's, in the program's place (for ``tpcds_join_agg``,
  bfloat16 values and products): the largest over a seed's tenants is
  what a run of the control would read, and the upper reading is the
  smallest of those over the seeds.

Prints one JSON line per seed, then a summary line with both readings.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))


def readings(dep, units: int, first_unit: int = 0) -> dict:
    app = dep.app
    queries = []
    for u in range(first_unit, first_unit + units):
        queries += dep.run_unit(u)
    program, control = [], []
    for i, t in enumerate(dep.tenants):
        ref = app.reference(dep.config, t)
        control.append(app.error(app.control(dep.config, t), ref))
        program += [app.error(q.answer, ref)
                    for q in queries if q.tenant == i]
    return {"program": max(program), "control": max(control),
            "errors": [q.error for q in queries if q.error]}


def main(argv=None, cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--units", type=int, default=1)
    args = ap.parse_args(argv)

    from benchlib import drive
    from benchlib.cell import load_cell
    from repro.compile_cache import enable_compile_cache
    from repro.obs import Tracer, set_tracer

    cell = cell or load_cell(args.workload)
    enable_compile_cache()
    set_tracer(Tracer(enabled=False))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        dep = drive.Deployment(cell.config, cell.traffic, seed)
        r = {"seed": seed, **readings(dep, args.units)}
        rows.append(r)
        print(json.dumps(r), flush=True)
        del dep
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows),
        "lower": max(r["program"] for r in rows),
        "upper": min(r["control"] for r in rows),
        "errors": sum(len(r["errors"]) for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
