"""The benchmark's definition resolves by name, keeps to its format, and
refuses to run without a TPU; its traffic is a function of the seed."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny
from bench import run as bench_run
from bench import traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = bench_tiny.load("BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(bench_tiny.ROOT, p))
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(bench_tiny.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_references_are_well_formed():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in metrics]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert {m["name"] for m in BENCH["end_to_end"]} == {
        "chip_s_per_step", "slowest_job_step_s", "setup_s"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                              "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m["workloads"]) <= set(CELLS)
    mux = {w["name"] for w in BENCH["workloads"] if "mux" in w["traffic"]}
    for name in ("switches_per_step", "switch_s_per_step"):
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert set(m["workloads"]) == mux
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(CELLS)
    assert all(w["chips"] == 1 and len(w["why"]) <= 200
               for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    got = bench_run.resolve(BENCH, cell)
    cfg = got["config"]
    assert cfg["name"] == got["workload"]["config"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == cfg["name"]]
    assert entry["file"].startswith("bench/") and entry["reduced"] == \
        cfg["reduced"]
    assert got["traffic"]["name"] == got["workload"]["traffic"]
    assert {m["name"] for m, _ in got["per_layer"]} == {
        m["name"] for m in BENCH["per_layer"] if cell in m["workloads"]}
    assert all(callable(read) for _, read in got["per_layer"])
    # every number the cell compares has a limit
    assert set(cfg["limits"]) >= {"gen_logp_err", "grad_err", "grad_med",
                                  "init_mismatch"}


def run_bench(root, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py"), *args],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=120)


def last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_refuses_a_platform_other_than_tpu():
    got = run_bench(bench_tiny.ROOT, "--workload", CELLS[0], "--seed",
                    "3000000001", "--seconds", "1", "--trace", "0")
    assert got.returncode != 0
    assert last_json(got.stdout) is None
    assert "TPU" in got.stderr


def copy_bench(dst):
    shutil.copytree(os.path.join(bench_tiny.ROOT, "bench"),
                    os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), dst)


def test_refuses_a_checkout_without_the_program(tmp_path):
    copy_bench(tmp_path)
    got = run_bench(str(tmp_path), "--workload", CELLS[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0",
                    env_extra={"PYTHONPATH": ""})
    assert got.returncode != 0 and last_json(got.stdout) is None


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    """A throwaway configuration, traffic mix and per-layer metric, added
    as files beside the others plus BENCHMARK.json entries: the harness
    finds all three by name with no existing file edited."""
    copy_bench(tmp_path)
    os.symlink(os.path.join(bench_tiny.ROOT, "src"), tmp_path / "src")
    before = {p: open(os.path.join(tmp_path, p), "rb").read()
              for p in ("bench/run.py", "bench/harness.py")}
    cfg = bench_tiny.qwen2_cfg()
    cfg["name"] = "qwen2-tiny"
    (tmp_path / "bench/configs/qwen2-tiny.json").write_text(json.dumps(cfg))
    tr = bench_tiny.traffic()
    tr["name"] = "tiny-solo"
    (tmp_path / "bench/traffic/tiny-solo.json").write_text(json.dumps(tr))
    (tmp_path / "bench/metrics/steps_credited.py").write_text(
        "def read(rec):\n    return rec['credited_steps']\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen2-tiny", "source": "test",
                             "file": "bench/configs/qwen2-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "qwen2-tiny.solo",
                               "config": "qwen2-tiny", "traffic": "tiny-solo",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_credited", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "client", "moves": "chip_s_per_step",
                               "workloads": ["qwen2-tiny.solo"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    got = run_bench(str(tmp_path), "--workload", "qwen2-tiny.solo", "--seed",
                    "9", "--seconds", "1", "--trace", "1")
    # it got past finding every file by name, to the look for a chip
    assert got.returncode == 3, got.stderr
    assert "TPU" in got.stderr
    for p, data in before.items():
        assert open(os.path.join(tmp_path, p), "rb").read() == data
    missing = run_bench(str(tmp_path), "--workload", "no.such-cell", "--seed",
                        "9", "--seconds", "1")
    assert missing.returncode != 0 and "no workload" in missing.stderr


@pytest.mark.parametrize("seed", [0, 3000000001, 2 ** 33 + 5, -7])
def test_traffic_is_a_function_of_the_seed(seed):
    tr = bench_tiny.load("bench", "traffic", "grpo-verify-mux2.json")
    a, b = traffic.plan(tr, seed), traffic.plan(tr, seed)
    assert [j.seed for j in a] == [j.seed for j in b]
    assert all(0 <= j.seed < 2 ** 31 for j in a)
    assert len({j.seed for j in a}) == len(a) == tr["jobs"]
    for ja, jb in zip(a, b):
        for step in range(1, 20):
            assert ja.verifier_s(step) == jb.verifier_s(step)
            np.testing.assert_array_equal(ja.rewards(step), jb.rewards(step))


def test_every_seed_gets_the_same_work():
    """Shapes are fixed; every seed spends the same verifier latency at
    every step, each block of ``strata`` steps the law's quantiles once;
    every group has mixed outcomes, so advantages are never all zero."""
    tr = bench_tiny.load("bench", "traffic", "grpo-verify-mux2.json")
    n = tr["verifier"]["strata"]
    blocks, runs = set(), set()
    for seed in (1, 2, 99, 2 ** 33 + 5):
        run = []
        for job in traffic.plan(tr, seed):
            run.append(tuple(job.verifier_s(s) for s in range(1, 4 * n)))
            for start in (1, n + 1, 2 * n + 3):
                blocks.add(tuple(sorted(job.verifier_s(s) for s in
                                        range(start, start + n))))
            for step in range(1, 10):
                r = job.rewards(step).reshape(-1, tr["group_size"])
                assert np.all((r.sum(1) > 0) & (r.sum(1) < tr["group_size"]))
                assert np.any(job.advantages(step) != 0)
        runs.add(tuple(run))
    assert len(runs) == 1
    (run,) = runs
    assert run[0] != run[1]       # the jobs do not stall in step
    assert len(blocks) == 1
    (lat,) = blocks
    assert lat[0] > 0 and lat[-1] <= tr["verifier"]["cap_s"]
    assert np.median(lat) == pytest.approx(tr["verifier"]["median_s"],
                                           rel=0.1)
