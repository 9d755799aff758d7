"""Scheduler microbenchmarks: HRRS vs FCFS on mixed queues, the §5.2
data-structure costs (segment-tree gang check, interval-set fitting) in
microseconds per call, deep-queue per-admission cost of the incremental
admission index vs Algorithm 1's full re-score, the dispatch plane's
concurrency gain + per-op control overhead (serial driver vs
Router.run_until_idle), the serve-mode submit->admission latency on an idle
persistent plane, the control plane's placement costs: cold/warm fit
decision latency vs resident-job count, the wall-clock of a realized
repack migration (hold -> drain -> StateManager.migrate -> rehome).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from repro.core import api
from repro.core.router import Router
from repro.core.scheduler import hrrs
from repro.core.scheduler.executor import TaskExecutor, VirtualClock
from repro.core.scheduler.intervals import IntervalSet
from repro.core.scheduler.placement import (NodeGroup, PlacementConfig,
                                            PlacementPolicy)
from repro.core.scheduler.repack_index import RepackIndex
from repro.core.scheduler.ring import CapacityRing
from repro.core.traces import synthetic_job_mix


class _SleepWPG:
    """Stub execution backend: sleep releases the GIL, so cross-group
    overlap through the concurrent dispatch plane is real."""

    def __init__(self, spec, sm, duration: float):
        self.spec = spec
        self.sm = sm
        self.exec_log = []
        self._duration = duration

    @property
    def job_prefix(self):
        return f"{self.spec.job_id}:{self.spec.deployment_id}"

    def resident(self):
        return False

    def ensure_resident(self):
        return 0.0

    def offload(self, to=None):
        return 0.0

    def execute(self, qop):
        if self._duration:
            time.sleep(self._duration)
        self.exec_log.append((qop.op.value, self._duration))
        return None


def _stub_router(n_groups: int, duration: float) -> tuple:
    router = Router(wpg_factory=lambda spec, sm: _SleepWPG(spec, sm,
                                                           duration))
    specs = []
    for g in range(n_groups):
        spec = api.DeploymentSpec(deployment_id=f"dep{g}", job_id=f"job{g}",
                                  model_name="stub", role="train")
        router.create_deployment(spec, group_id=g)
        specs.append(spec)
    return router, specs


def _dispatch_wall(n_groups: int, ops_per_group: int, duration: float,
                   concurrent: bool) -> float:
    router, specs = _stub_router(n_groups, duration)
    for spec in specs:
        for i in range(ops_per_group):
            router.submit_queued_operation(
                api.make_op(spec, api.Op.FORWARD, i))
    t0 = time.perf_counter()
    if concurrent:
        router.run_until_idle(timeout=60.0)
    else:
        router.drain()
    return time.perf_counter() - t0


def _serve_attach_latency_us(iters: int = 300) -> float:
    """submit -> admission latency on an IDLE serving plane: the parked
    worker must wake on the submit notification and start the op. Measured
    per op as ``t_started - t_submit`` (both on time.monotonic, the router's
    clock), median over ``iters`` one-at-a-time submissions so each lands on
    a fully idle plane."""
    router, specs = _stub_router(1, 0.0)
    lat = []
    with router:                      # serve() ... shutdown()
        for i in range(iters):
            qop = api.make_op(specs[0], api.Op.FORWARD, i)
            t0 = time.monotonic()
            fut = router.submit_queued_operation(qop)
            fut.wait(timeout=10.0)
            lat.append(router.executor.tasks[qop.req_id].t_started - t0)
            router.wait_idle(timeout=10.0)
    return float(np.median(lat) * 1e6)


def _mixed_queue(n: int, seed: int = 0, equal_exec: bool = False):
    rng = np.random.default_rng(seed)
    return [hrrs.Request(req_id=i, job_id=f"job{rng.integers(0, 4)}",
                         op="update_actor",
                         exec_time=30.0 if equal_exec
                         else float(rng.uniform(5, 60)),
                         arrival_time=float(rng.uniform(0, 100)))
            for i in range(n)]


def _time_us(fn, iters=200, repeats=4) -> float:
    """Mean per-call latency over the best of ``repeats`` timing chunks,
    with the cyclic GC paused inside the timed region.

    Best-of-repeats is the ``timeit`` recommendation: interference (VM
    steal, frequency scaling, another bench row's leftover heap) only ever
    ADDS time, so the minimum chunk is the closest estimate of the true
    cost. GC pauses otherwise charge whichever row happens to trip a
    collection for garbage produced by earlier rows."""
    per_chunk = max(1, iters // repeats)
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(per_chunk):
                fn()
            best = min(best, (time.perf_counter() - t0) / per_chunk)
    finally:
        if was_enabled:
            gc.enable()
    return best * 1e6


def _admission_us(n_queued: int, n_jobs: int, use_index: bool,
                  seed: int = 0, mixed_priority: bool = False) -> float:
    """Per-admission cost of ``n_queued`` ops through the executor's
    submit + pick/start/finish cycle on one group: the dispatch plane's hot
    path. Submissions are INSIDE the timed region so the indexed path is
    charged for its O(log n) insert maintenance, not just the pick.
    ``mixed_priority`` assigns each job a distinct tenant priority weight,
    exercising the kinetic tournament's extra crossing class — the flat-cost
    claim must survive the multi-tenant score term."""
    clock = VirtualClock()
    ex = TaskExecutor(now=clock, policy="hrrs",
                      use_admission_index=use_index)
    rng = np.random.default_rng(seed)
    prio_of = {f"job{j}": (0.5, 1.0, 2.0, 4.0)[j % 4]
               for j in range(n_jobs)} if mixed_priority else {}
    reqs = [hrrs.Request(req_id=i + 1, job_id=f"job{i % n_jobs}",
                         op="update_actor",
                         exec_time=float(rng.uniform(0.5, 8.0)),
                         arrival_time=0.0,
                         priority=prio_of.get(f"job{i % n_jobs}", 1.0))
            for i in range(n_queued)]
    gaps = [float(rng.uniform(0.0, 0.2)) for _ in range(n_queued)]
    admitted = 0
    t0 = time.perf_counter()
    for r, gap in zip(reqs, gaps):
        r.arrival_time = clock.now()
        ex.submit(r, group_id=0)
        clock.advance(gap)
    while True:
        task = ex.pick_next(0)
        if task is None:
            break
        ex.try_start(task)
        ex.finish(task)
        clock.advance(0.05)
        admitted += 1
    dt = time.perf_counter() - t0
    assert admitted == n_queued
    return dt / n_queued * 1e6


def _placement_decision_us(n_resident: int, seed: int = 0) -> tuple:
    """Cold + warm fit latency against a fleet already hosting
    ``n_resident`` placed jobs (the §4.3.2 decision hot path)."""
    horizon = 28_800.0
    n_groups = max(4, n_resident // 4)
    pol = PlacementPolicy(
        [NodeGroup(g, 8, IntervalSet([(0.0, horizon)]))
         for g in range(n_groups)],
        PlacementConfig(horizon=horizon))
    profiles = synthetic_job_mix(n_resident + 1, seed=seed)
    for i, p in enumerate(profiles[:-1]):
        pol.place_warm(f"res{i}", p.mean_trace())
    probe = profiles[-1].mean_trace()
    # one spare empty group so the cold probe always has a clean target
    pol.add_group(NodeGroup(n_groups, 8, IntervalSet([(0.0, horizon)])))

    def warm_probe():
        assert pol.place_warm("probe", probe) is not None
        pol.remove("probe")

    def cold_probe():
        assert pol.place_cold("probe", 1, 600.0) is not None
        pol.remove("probe")

    return _time_us(cold_probe, iters=50), _time_us(warm_probe, iters=20)


def _repack_plan_us(n_resident: int, seed: int = 0) -> float:
    """Latency of ONE incremental repack planning pass
    (``PlacementPolicy.plan_repack``) against a fleet hosting
    ``n_resident`` placed jobs: the reconciler's periodic decision cost
    (clone + per-job re-fit + interference deltas; no mutation)."""
    horizon = 28_800.0
    n_groups = max(4, n_resident // 4)
    pol = PlacementPolicy(
        [NodeGroup(g, 8, IntervalSet([(0.0, horizon)]))
         for g in range(n_groups)],
        PlacementConfig(horizon=horizon))
    profiles = synthetic_job_mix(n_resident, seed=seed)
    for i, p in enumerate(profiles):
        pol.place_warm(f"res{i}", p.mean_trace())
    iters = max(2, 64 // max(n_resident, 1))
    return _time_us(lambda: pol.plan_repack(origin=0.0, min_gain=0.001),
                    iters=iters)


def _repack_plan_inc_us(n_resident: int, seed: int = 0,
                        dirty_groups: int = 2, iters: int = 40) -> float:
    """Steady-state latency of one INCREMENTAL repack planning pass
    (``RepackIndex.plan``) against a fleet hosting ``n_resident`` placed
    jobs. Per pass, ``dirty_groups`` groups are flagged as drifted (the
    reconciler's occupancy-drift trigger); candidates come from those
    groups only and destination search is bound-pruned and capped exactly
    as ``DirectorConfig`` defaults configure the shipped reconcile path.
    The full ``plan_repack`` oracle re-fits every job on a policy clone
    instead — O(jobs x groups) per pass."""
    horizon = 28_800.0
    n_groups = max(4, n_resident // 4)
    pol = PlacementPolicy(
        [NodeGroup(g, 8, IntervalSet([(0.0, horizon)]))
         for g in range(n_groups)],
        PlacementConfig(horizon=horizon))
    profiles = synthetic_job_mix(n_resident, seed=seed)
    for i, p in enumerate(profiles):
        pol.place_warm(f"res{i}", p.mean_trace())
    idx = RepackIndex(pol)
    # converge first: drain the move backlog the initial placement leaves
    # behind so the timed passes measure steady-state drift response, not
    # a cold start (the first pass sees every group dirty)
    for _ in range(4):
        plan = idx.plan(origin=0.0, min_gain=0.001, max_dest_search=12)
        if not plan.deltas:
            break
        pol.apply_repack(plan)
    gids = sorted(g.group_id for g in pol.groups)
    cursor = [0]

    def drift_pass():
        for k in range(dirty_groups):
            idx.mark_dirty(gids[(cursor[0] + k) % len(gids)])
        cursor[0] += dirty_groups
        idx.plan(origin=0.0, min_gain=0.001, max_dest_search=12)

    drift_pass()     # warm the per-group summary cache (steady state)
    return _time_us(drift_pass, iters=iters)


def _repack_migrate_s(nbytes: int = 8 << 20) -> float:
    """Wall-clock of ONE realized repack migration through
    ``Router.reassign_job``: admission hold, in-flight drain,
    StateManager.migrate of ~nbytes of managed state, queued-op rehome,
    release. Queued ops survive and complete on the destination group."""
    router, specs = _stub_router(2, 0.0)
    spec = specs[0]
    wpg = router.wpgs[spec.deployment_id]
    sm = router.state_managers[0]
    n_arrays = 8
    arr = np.ones((nbytes // n_arrays // 4,), np.float32)
    for i in range(n_arrays):
        sm.register(wpg.job_prefix, {f"w{i}": arr})
    queued = [router.submit_queued_operation(
        api.make_op(spec, api.Op.FORWARD, i)) for i in range(16)]
    t0 = time.perf_counter()
    router.reassign_job(spec.job_id, 1)
    dt = time.perf_counter() - t0
    router.drain()
    for f in queued:
        f.result()
    return dt


def run() -> list[tuple[str, float, str]]:
    rows = []
    # HRRS vs FCFS: switches on a comparable-service-time queue — the regime
    # where HRRS's switch-amortising guarantee is unconditional (§4.4; with
    # wildly unequal exec times HRRN's shortest-first pressure can trade a
    # switch for responsiveness)
    q = _mixed_queue(64, seed=2, equal_exec=True)
    plan_h = hrrs.schedule(None, None, [hrrs.Request(**vars(r)) for r in q],
                           100.0, None, t_load=10.0, t_offload=10.0)
    plan_f = hrrs.fcfs_schedule(None, None, [hrrs.Request(**vars(r)) for r in q],
                                100.0, None, t_load=10.0, t_offload=10.0)
    rows.append(("hrrs/switches", hrrs.total_switches(plan_h),
                 f"fcfs={hrrs.total_switches(plan_f)}"))
    rows.append(("hrrs/makespan_s", hrrs.makespan(plan_h),
                 f"fcfs={hrrs.makespan(plan_f):.0f}"))
    assert hrrs.total_switches(plan_h) <= hrrs.total_switches(plan_f)
    # heterogeneous queue: report both (no ordering guarantee)
    q2 = _mixed_queue(64, seed=3)
    plan_h2 = hrrs.schedule(None, None, [hrrs.Request(**vars(r)) for r in q2],
                            100.0, None, t_load=10.0, t_offload=10.0)
    plan_f2 = hrrs.fcfs_schedule(None, None,
                                 [hrrs.Request(**vars(r)) for r in q2],
                                 100.0, None, t_load=10.0, t_offload=10.0)
    rows.append(("hrrs/switches_hetero", hrrs.total_switches(plan_h2),
                 f"fcfs={hrrs.total_switches(plan_f2)}"))

    # scheduling-call latency
    us = _time_us(lambda: hrrs.schedule(
        None, None, [hrrs.Request(**vars(r)) for r in q], 100.0, None,
        10.0, 10.0), iters=50)
    rows.append(("hrrs/schedule_64req_us", us, ""))

    # §5.2.1 segment-tree gang-feasibility on the full 28 800-slot ring
    ring = CapacityRing(2048, slots=28_800)
    for i in range(64):
        ring.reserve(i * 400.0, 120.0, 16)
    us = _time_us(lambda: ring.feasible(7_000.0, 600.0, 64), iters=2_000)
    rows.append(("ring/gang_check_us", us, "O(log 28800)"))

    # interval-set simulate_insert (bisect fitting)
    iv = IntervalSet([(i * 100.0, i * 100.0 + 60.0) for i in range(200)])
    segs = [(5.0, 20.0), (130.0, 25.0), (410.0, 30.0)]
    us = _time_us(lambda: iv.simulate_insert(segs, shift=3.0), iters=5_000)
    rows.append(("intervals/simulate_insert_us", us, "O(N log M)"))

    # deep-queue admission: incremental index vs Algorithm 1 full re-score,
    # multiple jobs multiplexed per group (the §4.4 control-plane hot path)
    for n in (64, 256, 1024):
        full_us = _admission_us(n, n_jobs=4, use_index=False)
        idx_us = _admission_us(n, n_jobs=4, use_index=True)
        rows.append((f"admission/full_rescore_n{n}_us", full_us,
                     "per admission, 4 jobs/group"))
        rows.append((f"admission/indexed_n{n}_us", idx_us,
                     f"speedup={full_us / max(idx_us, 1e-9):.1f}x"))
    # deep-queue extension: the indexed path stays flat at 4096 (the full
    # re-score is omitted there — O(n^2) total, ~30 s for one row)
    rows.append(("admission/indexed_n4096_us",
                 _admission_us(4096, n_jobs=4, use_index=True),
                 "full re-score omitted at this depth"))
    # multi-tenant priority term: a mixed-priority pool (weights 0.5/1/2/4
    # across the job buckets) exercises the tournament's extra flat-level
    # crossing class; indexed admission must stay flat with the term on
    for n in (256, 1024):
        pf = _admission_us(n, n_jobs=4, use_index=False, mixed_priority=True)
        pi = _admission_us(n, n_jobs=4, use_index=True, mixed_priority=True)
        rows.append((f"admission/priority_full_n{n}_us", pf,
                     "mixed-priority pool, full re-score"))
        rows.append((f"admission/priority_indexed_n{n}_us", pi,
                     f"speedup={pf / max(pi, 1e-9):.1f}x"))

    # control plane: placement decision latency vs resident-job count, and
    # the wall-clock of a realized repack migration (8 MiB managed state)
    for n_res in (4, 16, 64):
        cold_us, warm_us = _placement_decision_us(n_res)
        rows.append((f"placement/decision_cold_n{n_res}_us", cold_us,
                     f"{n_res} resident jobs"))
        rows.append((f"placement/decision_warm_n{n_res}_us", warm_us,
                     "micro-shift fit + interference rank"))
    rows.append(("placement/repack_migrate_s", _repack_migrate_s(),
                 "hold+drain+migrate(8MiB)+rehome, 16 queued ops"))
    # reconciler: incremental repack PLANNING latency vs resident-job count
    # (plan-only — the realized moves are priced by repack_migrate_s above,
    # which also feeds the planner's migration-cost floor)
    for n_res in (4, 16, 64):
        rows.append((f"placement/repack_plan_n{n_res}_us",
                     _repack_plan_us(n_res),
                     f"full plan_repack over {n_res} resident jobs"))
    # fleet scale: the incremental RepackIndex (the shipped reconcile
    # path — dirty-group candidates, bound-pruned + capped destination
    # search) vs the full oracle; the full re-fit is O(jobs x groups) and
    # is omitted at n=1024 (tens of seconds for one row)
    full256 = _repack_plan_us(256)
    rows.append(("placement/repack_plan_full_n256_us", full256,
                 "full plan_repack, O(jobs x groups)"))
    inc256 = _repack_plan_inc_us(256)
    rows.append(("placement/repack_plan_inc_n256_us", inc256,
                 f"RepackIndex, speedup={full256 / max(inc256, 1e-9):.0f}x"))
    rows.append(("placement/repack_plan_n1024_us", _repack_plan_inc_us(1024),
                 "RepackIndex (shipped path); full re-fit omitted here"))

    # dispatch plane: cross-group overlap (4 groups x 6 x 10ms ops) and the
    # per-op control overhead of the concurrent driver on zero-cost ops
    w_serial = _dispatch_wall(4, 6, 0.01, concurrent=False)
    w_conc = _dispatch_wall(4, 6, 0.01, concurrent=True)
    rows.append(("dispatch/overlap_speedup", w_serial / max(w_conc, 1e-9),
                 f"serial={w_serial * 1e3:.0f}ms conc={w_conc * 1e3:.0f}ms"))
    n_ops = 200
    w0 = _dispatch_wall(1, n_ops, 0.0, concurrent=True)
    rows.append(("dispatch/op_overhead_us", w0 / n_ops * 1e6,
                 "run_until_idle, zero-cost ops"))
    # serve mode: submit -> admission latency against an idle persistent
    # plane (the parked worker's wakeup path, pinned so regressions show)
    rows.append(("dispatch/serve_attach_latency_us",
                 _serve_attach_latency_us(),
                 "median, idle serve() plane"))
    return rows


if __name__ == "__main__":
    import argparse
    import os
    import sys

    # run as a script, the checkout root is not on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.run import BENCH_JSON, write_bench_json

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=BENCH_JSON,
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args()
    rows = run()
    for r in rows:
        print(r)
    if args.json:
        write_bench_json(rows, args.json)
        print(f"wrote {args.json} ({len(rows)} rows)")
