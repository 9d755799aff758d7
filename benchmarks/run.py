"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV and writes a machine-readable
``BENCH_<pr>.json`` (row name -> {value, units}) so the performance
trajectory is tracked across PRs. Run:

    PYTHONPATH=src python -m benchmarks.run [--json BENCH_PR10.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

BENCH_JSON = "BENCH_PR10.json"


def write_bench_json(rows: list, path: str) -> None:
    """Persist bench rows as ``{name: {"value": ..., "units": ...}}``.
    ``units`` carries the human-readable derived/context column."""
    out = {}
    for name, value, derived in rows:
        try:
            value = float(value)
        except (TypeError, ValueError):
            value = str(value)
        out[name] = {"value": value, "units": str(derived)}
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")


def main() -> None:
    import benchmarks.fig2_dp_mfu as fig2
    import benchmarks.fig7_cost as fig7
    import benchmarks.fig7c_auc as fig7c
    import benchmarks.fig8_policies as fig8
    import benchmarks.table2_bubble as table2
    import benchmarks.hrrs_bench as hrrsb
    import benchmarks.mesh_bench as meshb
    import benchmarks.roofline as roofline

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=BENCH_JSON,
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args()

    modules = [
        ("fig2_dp_mfu", fig2),
        ("fig7_cost", fig7),
        ("fig7c_auc", fig7c),
        ("fig8_policies", fig8),
        ("table2_bubble", table2),
        ("hrrs_bench", hrrsb),
        ("mesh_bench", meshb),
        ("roofline", roofline),
    ]
    print("name,value,derived")
    failed = []
    all_rows = []
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001
            failed.append((name, repr(e)))
            print(f"{name}/ERROR,nan,{e!r}")
            continue
        for row_name, value, derived in rows:
            print(f"{row_name},{value},{derived}")
        all_rows.extend(rows)
        elapsed = ((f"{name}/elapsed_s", round(time.time() - t0, 2), ""))
        print(f"{elapsed[0]},{elapsed[1]},")
        all_rows.append(elapsed)
    if args.json:
        write_bench_json(all_rows, args.json)
        print(f"wrote {args.json} ({len(all_rows)} rows)", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
