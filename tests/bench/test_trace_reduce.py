"""The trace reduction and the per-layer readers on small traces."""
from __future__ import annotations

import importlib.util
import os
import time

import pytest

import bench_tiny
from bench import harness, traffic
from bench.trace_reduce import (Trace, find_xplane, gaps, name_gap, overlap,
                                total, union)

MS = 1_000_000  # ns


def reader(name):
    path = os.path.join(bench_tiny.ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def synthetic() -> Trace:
    """A 100 ms window on one chip: a GENERATE span 0-30 ms with ops 5-25
    ms, a verifier stall 30-60 ms with one stray op 30-35 ms, a switch
    60-70 ms with no op, an update 70-100 ms with ops 70-90 ms."""
    ops = {"/device:TPU:0": [
        (5 * MS, 15 * MS, "fusion.1"), (10 * MS, 25 * MS, "dot.2"),
        (30 * MS, 35 * MS, "copy.3"), (70 * MS, 90 * MS, "dot.2"),
        (120 * MS, 130 * MS, "dot.2")]}            # after the window
    spans = {"window": [(0, 100 * MS)], "GENERATE": [(0, 30 * MS)],
             "verifier": [(30 * MS, 60 * MS)], "switch": [(60 * MS, 70 * MS)],
             "UPDATE_ACTOR": [(70 * MS, 100 * MS)]}
    return Trace(ops, spans)


def test_interval_arithmetic():
    merged = union([(5, 10), (0, 3), (2, 4), (10, 12), (20, 20)])
    assert merged == [(0, 4), (5, 12)]
    assert total(merged) == 11
    assert overlap(merged, [(3, 6), (11, 30)]) == 1 + 1 + 1
    assert gaps(merged, 0, 15) == [(4, 5), (12, 15)]
    assert gaps([], 0, 7) == [(0, 7)]


def test_busy_union_idle_share_and_breakdown():
    red = synthetic().reduce()
    assert red["window_s"] == pytest.approx(0.1)
    assert red["chips"] == 1
    # 5-25, 30-35, 70-90 ms: the overlapping ops count once
    assert red["busy_s"] == pytest.approx(0.045)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["dot.2"] == pytest.approx(0.035)      # 10-25 and 70-90 ms
    assert ops["fusion.1"] == pytest.approx(0.010)
    assert "copy.3" in ops and len(ops) == 3
    idle = read_all(red)["device_idle_share"]
    assert idle == pytest.approx(55.0)


def test_gap_named_by_the_span_open_in_it():
    red = synthetic().reduce()
    named = red["breakdown"]["idle_gaps"]
    # gaps: 0-5 (GENERATE), 25-30 (GENERATE), 35-70 (verifier 25 ms,
    # switch 10 ms), 90-100 (UPDATE_ACTOR)
    assert named[0] == ["verifier", pytest.approx(0.035)]
    assert sorted(n for n, _ in named) == ["GENERATE", "GENERATE",
                                           "UPDATE_ACTOR", "verifier"]
    assert name_gap((200, 300), {"verifier": [(0, 100)]}) == "dispatch"


def read_all(red, credited=2.0, switches=3):
    rec = dict(trace=red, credited_steps=credited, window_s=red["window_s"],
               chips=1, flops=1.97e12, peak_flops=197e12, hbm_bytes=16e9,
               memory_peak_bytes=8e9, switches_in_window=switches)
    names = ["stall_fill_share", "switches_per_step", "switch_s_per_step",
             "generate_s_per_step", "update_s_per_step", "mfu",
             "device_idle_share", "hbm_peak_share"]
    return {n: reader(n)(rec) for n in names}


def test_readers_on_the_synthetic_trace():
    got = read_all(synthetic().reduce())
    assert got["stall_fill_share"] == pytest.approx(100 * 5 / 30)
    assert got["switches_per_step"] == pytest.approx(1.5)
    assert got["switch_s_per_step"] == pytest.approx(0.010 / 2)
    assert got["generate_s_per_step"] == pytest.approx(0.020 / 2)
    assert got["update_s_per_step"] == pytest.approx(0.020 / 2)
    assert got["mfu"] == pytest.approx(100 * 1.97e12 / (0.1 * 197e12))
    assert got["hbm_peak_share"] == pytest.approx(50.0)


def test_readers_find_nothing_without_a_trace():
    rec = dict(trace=None, credited_steps=2.0, window_s=10.0, chips=1,
               flops=None, peak_flops=197e12, hbm_bytes=16e9,
               memory_peak_bytes=0, switches_in_window=None)
    for name in ("stall_fill_share", "switches_per_step", "switch_s_per_step",
                 "generate_s_per_step", "update_s_per_step", "mfu",
                 "device_idle_share", "hbm_peak_share"):
        assert reader(name)(rec) is None, name


def test_host_spans_read_back_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.verifier"):
            time.sleep(0.02)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = Trace.from_file(find_xplane(str(tmp_path)))
    lo, hi = tr.window()
    assert hi - lo >= 20 * MS
    (s, e), = tr.spans["verifier"]
    assert lo <= s < e <= hi and e - s >= 20 * MS
    red = tr.reduce()
    assert red["spans"]["verifier"] == [(s, e)]


def test_watched_span_lasts_until_the_outputs_are_ready(tmp_path):
    """A traced call's span runs on, off the caller's thread, until its
    outputs are ready; an untraced run starts no watcher."""
    import jax
    import jax.numpy as jnp
    slow = jax.jit(lambda x: jax.lax.fori_loop(
        0, 300, lambda i, y: jnp.tanh(y @ x), x))
    x = jnp.full((256, 256), 1e-3)
    t = time.perf_counter()
    slow(x).block_until_ready()
    busy = time.perf_counter() - t
    cfg, tr = bench_tiny.qwen2_cfg(), bench_tiny.traffic()
    untraced = harness.Run(cfg, tr, 1, 1.0)
    untraced._watch("OP", slow(x))
    assert untraced._watchers == []
    run = harness.Run(cfg, tr, 1, 1.0, trace_dir=str(tmp_path))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.OP"):
        out = slow(x)
    t = time.perf_counter()
    run._watch("OP", {"y": out, "n": 3})
    assert time.perf_counter() - t < busy / 2
    run._stop_trace()
    spans = Trace.from_file(find_xplane(str(tmp_path))).spans["OP"]
    assert len(spans) == 2
    assert total(union(spans)) >= 0.8 * busy * 1e9


@pytest.mark.parametrize("steps, credit", [
    ([[0.0, 10.0], [10.0, 20.0], [20.0, 30.0]], 1.0),   # covers the window
    ([[5.0, 15.0]], 0.5),                               # straddles the start
    ([[18.0, 22.0]], 0.5),                              # straddles the end
    ([[8.0, 24.0]], 10.0 / 16.0),                       # covers it and more
    ([[30.0, 40.0], [0.0, 9.0]], 0.0),                  # outside it
    ([[12.0, None]], 0.0),                              # never finished
])
def test_window_credit(steps, credit):
    job = harness.JobRec(traffic.plan(bench_tiny.traffic(), 1)[0])
    job.steps = steps
    assert job.credit(10.0, 20.0) == pytest.approx(credit)


def test_attempted_and_failed_count_steps_that_touch_the_window():
    job = harness.JobRec(traffic.plan(bench_tiny.traffic(), 1)[0])
    job.steps = [[0.0, 9.0], [9.0, 15.0], [15.0, 25.0], [25.0, None]]
    assert harness.attempted_failed({"a": job}, (10.0, 20.0)) == (2, 0)
    assert harness.attempted_failed({"a": job}, (10.0, 30.0)) == (3, 1)
