#!/usr/bin/env python3
"""Read the readings a cell's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 2

For each seed, in one process: one run of the cell (set-up, a short
window, the drain), then for every job the numbers of the program, of the
float8 control put in its place, and of the planted faults (half of each
batch left out of the update, one served token altered, a step that leaves
the state unchanged), each with the verdict the cell's limits give it. One
JSON line per seed on standard output. Exits 1 if the program came out not
correct, or the control or a fault came out correct, on any seed. The
benchmark's own runs never do this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run as bench_run
    cell = bench_run.resolve(bench_run.load_json("BENCHMARK.json"),
                             args.workload)

    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    import jax
    import numpy as np
    jax.config.update("jax_compilation_cache_dir", bench_run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("calibration needs a TPU", file=sys.stderr)
        return 3

    from bench import correctness, harness
    t_start = T_START
    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell["config"], cell["traffic"], seed, args.seconds,
                          chips=cell["workload"]["chips"], t_start=t_start)
        out = run.run()
        t_ref = time.monotonic()
        state = run.state_numbers()
        limits = harness.limits_for(cell["config"], len(run.jobs))
        jobs = []
        for rec in run.job_records():
            got = correctness.calibration_numbers(
                run.reference, rec, cell["traffic"]["prompt_len"],
                np.random.default_rng([seed % 2 ** 64, 7]))
            got["correct"] = harness.verdicts(state, got, limits)
            sound &= all(ok == (name == "program")
                         for name, ok in got["correct"].items())
            jobs.append(got)
        line = {"seed": seed, "setup_s": out["setup_s"], "state": state,
                "memory_peak_bytes": out["memory_peak_bytes"],
                "switch_checked": run.switch_checked,
                "switch_mismatch": run.switch_mismatch,
                "init_equal": [r.init_equal for r in run.jobs.values()],
                "reference_s": time.monotonic() - t_ref, "jobs": jobs}
        print(json.dumps(line), flush=True)
        t_start = time.monotonic()
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
