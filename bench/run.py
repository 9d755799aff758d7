#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` at the checkout root: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, and one reader ``bench/metrics/<metric>.py``
per per-layer metric. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window and reports its per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its limit);
the last lines of standard error give the same numbers. A machine without
enough TPU chips, or a checkout without the program, exits non-zero and
prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(*words):
    print(*words, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_reader(name: str):
    import importlib.util
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, cell: str) -> dict:
    """Everything one cell names, found by name; a missing file or entry
    raises."""
    work = {w["name"]: w for w in bench["workloads"]}
    if cell not in work:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    w = work[cell]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return dict(
        workload=w,
        config=load_json(cfg_entry["file"]),
        traffic=load_json("bench", "traffic", f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if for_cell(m, cell)],
        per_layer=[(m, load_reader(m["name"])) for m in bench["per_layer"]
                   if for_cell(m, cell)],
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    cell = resolve(load_json("BENCHMARK.json"), args.workload)
    chips = cell["workload"]["chips"]

    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"this cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)")
        return 3
    peaks = load_json("bench", "peaks.json")[devs[0].device_kind]

    from bench import harness
    trace_dir = (harness.trace_dir_for(ROOT, args.workload)
                 if args.trace else None)
    run = harness.Run(cell["config"], cell["traffic"], args.seed, args.seconds,
                      chips=chips, trace_dir=trace_dir, t_start=T_START)
    out = run.run()
    attempted, failed = harness.attempted_failed(run.jobs, out["window"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    result = {}
    if args.trace:
        rec = harness.run_record(run, out, peaks)
        units = {m["name"]: m["unit"] for m, _ in cell["per_layer"]}
        metrics = {}
        for m, read in cell["per_layer"]:
            value = read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        device.update(busy_s=rec["trace"]["busy_s"],
                      window_s=rec["trace"]["window_s"])
        result["breakdown"] = rec["trace"]["breakdown"]
    else:
        e2e = harness.end_to_end(run, out)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    numbers = run.numbers()
    chk = harness.checks(numbers, harness.limits_for(cell["config"],
                                                     len(run.jobs)))
    correct = harness.is_correct(chk) and failed == 0
    log("steps done per job:", dict(harness.steps_summary(run.jobs)))
    log("compiles in window not served by the cache:",
        out["compiles_in_window"])
    log("initial leaves equal to the benchmark's draw:",
        {j: r.init_equal for j, r in run.jobs.items()})
    log("switch round trips checked:", run.switch_checked)
    log("device bytes in use once the program's state was freed:",
        out["bytes_in_use_after"])
    log("numbers read:", json.dumps(numbers))
    for name, c in chk.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    line.update(result)
    line["checks"] = chk
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # noqa: BLE001 - a failed run prints no result
        traceback.print_exc()
        sys.exit(1)
