"""Benchmark aggregator — one section per paper table/figure plus the
roofline report. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run \
        [--only fig4|fig7|fig8|roofline|executor|sharing|faults|dataplane|
               elastic|tiering]
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import (
    bench_dataplane,
    bench_elastic,
    bench_executor,
    bench_faults,
    bench_sharing,
    bench_skew,
    bench_tiering,
    fig4_join,
    fig7_query,
    fig8_sharing,
    roofline,
)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["fig4", "fig7", "fig8", "roofline", "executor",
                             "sharing", "faults", "dataplane", "elastic",
                             "tiering", "skew"])
    args = ap.parse_args(argv)

    sections = {
        "fig4": fig4_join.main,
        "fig7": fig7_query.main,
        "fig8": fig8_sharing.main,
        "roofline": roofline.main,
        "executor": bench_executor.main,
        "sharing": bench_sharing.main,
        "faults": bench_faults.main,
        "dataplane": bench_dataplane.main,
        "elastic": bench_elastic.main,
        "tiering": bench_tiering.main,
        "skew": bench_skew.main,
    }
    if args.only:
        sections = {args.only: sections[args.only]}

    rows: list = []
    for name, fn in sections.items():
        try:
            fn(rows)
        except Exception as e:  # noqa: BLE001 - report and continue
            rows.append((f"{name}/ERROR:{type(e).__name__}", 0.0, 0.0))
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
