"""The concurrent event-driven dispatch plane (Router.run_until_idle).

Covers the §5.1/§5.2 runtime properties the serial loop could not provide:
- cross-group wall-clock overlap (measured against the serial driver on the
  SAME admission path),
- per-group mutual exclusion + prerequisite ordering under concurrency,
- thread-safe Future semantics (wait timeout, error propagation, poisoned
  dependents),
- deterministic HRRS admission under a VirtualClock,
- pending-table cleanup and incremental cluster billing.

Worker-process groups are replaced by sleep-based stubs (time.sleep releases
the GIL, so overlap measurements are real) injected through the Router's
``wpg_factory`` — no model build, so this module stays fast.
"""
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import api
from repro.core.cluster import BillingRecord, PlexCluster
from repro.core.router import Router
from repro.core.scheduler.executor import State, VirtualClock
from repro.core.state_manager import StateManager


class StubWPG:
    """Minimal execution backend: records (deployment, req_id, t0, t1) into a
    shared trace; ops with kwargs {'fail': True} raise."""

    def __init__(self, spec, sm, duration, trace):
        self.spec = spec
        self.sm = sm
        self.exec_log = []
        self._duration = duration
        self._trace = trace

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
        t0 = time.monotonic()
        if self._duration:
            time.sleep(self._duration)
        if qop.kwargs.get("fail"):
            raise RuntimeError(f"op {qop.req_id} failed (injected)")
        t1 = time.monotonic()
        self._trace.append((self.spec.deployment_id, qop.req_id, t0, t1))
        self.exec_log.append((qop.op.value, t1 - t0))
        return {"req_id": qop.req_id}


def make_router(n_groups=2, duration=0.03, now=time.monotonic,
                policy="hrrs"):
    trace = []
    router = Router(now=now, policy=policy,
                    wpg_factory=lambda spec, sm: StubWPG(spec, sm, duration,
                                                         trace))
    specs = []
    for g in range(n_groups):
        spec = api.DeploymentSpec(deployment_id=f"dep{g}",
                                  job_id=f"job{g}", model_name="stub",
                                  role="train")
        router.create_deployment(spec, group_id=g)
        specs.append(spec)
    return router, specs, trace


def submit_batch(router, spec, n, **kwargs):
    return [router.submit_queued_operation(
        api.make_op(spec, api.Op.FORWARD, i, **kwargs)) for i in range(n)]


# --------------------------------------------------------------- overlap
def test_two_groups_overlap_beats_serial_wall_clock():
    """Acceptance: two jobs on two groups under the concurrent plane finish
    in < 0.9x the wall-clock of the identical workload on the serial
    driver."""
    ops_per_group, dur = 4, 0.05

    r1, specs1, _ = make_router(n_groups=2, duration=dur)
    for s in specs1:
        submit_batch(r1, s, ops_per_group)
    t0 = time.monotonic()
    n_serial = r1.drain()
    serial_wall = time.monotonic() - t0

    r2, specs2, trace2 = make_router(n_groups=2, duration=dur)
    for s in specs2:
        submit_batch(r2, s, ops_per_group)
    t0 = time.monotonic()
    n_conc = r2.run_until_idle(timeout=30.0)
    conc_wall = time.monotonic() - t0

    assert n_serial == n_conc == 2 * ops_per_group
    assert conc_wall < 0.9 * serial_wall, (conc_wall, serial_wall)
    # measured overlap: some dep0 interval intersects some dep1 interval
    by_dep = {}
    for dep, _, a, b in trace2:
        by_dep.setdefault(dep, []).append((a, b))
    overlaps = any(a0 < b1 and a1 < b0
                   for a0, b0 in by_dep["dep0"]
                   for a1, b1 in by_dep["dep1"])
    assert overlaps, "no cross-group wall-clock overlap observed"


# -------------------------------------------------- per-group exclusivity
def test_per_group_serial_ordering_under_concurrency():
    r, specs, trace = make_router(n_groups=2, duration=0.01)
    futs = [submit_batch(r, s, 5) for s in specs]
    r.run_until_idle(timeout=30.0)
    for group_futs in futs:
        for f in group_futs:
            assert f.done() and f.result()["req_id"] > 0
    # within one deployment (== one group lock) intervals never overlap
    by_dep = {}
    for dep, req_id, a, b in trace:
        by_dep.setdefault(dep, []).append((a, b))
    for dep, spans in by_dep.items():
        assert len(spans) == 5
        spans.sort()
        for (a0, b0), (a1, b1) in zip(spans, spans[1:]):
            assert b0 <= a1 + 1e-6, f"{dep}: ops overlapped on one group"
    # executor left clean: everything completed, locks free
    assert all(t.state == State.COMPLETED
               for t in r.executor.tasks.values())
    assert all(lock.holder is None for lock in r.executor.locks.values())


def test_prerequisite_chain_order_preserved_concurrently():
    r, specs, trace = make_router(n_groups=1, duration=0.005)
    spec = specs[0]
    prev, chain = (), []
    for i in range(6):
        qop = api.make_op(spec, api.Op.FORWARD, i, prerequisites=prev)
        r.submit_queued_operation(qop)
        chain.append(qop.req_id)
        prev = (qop.req_id,)
    r.run_until_idle(timeout=30.0)
    executed = [req_id for _, req_id, _, _ in trace]
    assert executed == chain


# ------------------------------------------------- callback resubmission
def test_callback_submitted_followups_keep_plane_alive():
    """A future callback submitting follow-up work (the controller's
    generate -> update chain) must be executed before run_until_idle
    declares the cluster idle."""
    r, specs, trace = make_router(n_groups=2, duration=0.01)
    seen = []

    def chain(spec, depth):
        def on_done(fut):
            seen.append(fut.result()["req_id"])
            if depth > 0:
                f2 = r.submit_queued_operation(
                    api.make_op(spec, api.Op.FORWARD, depth))
                f2.add_done_callback(chain(spec, depth - 1))
        return on_done

    for s in specs:
        f = r.submit_queued_operation(api.make_op(s, api.Op.FORWARD, 0))
        f.add_done_callback(chain(s, 3))
    n = r.run_until_idle(timeout=30.0)
    assert n == 2 * 4                 # initial op + 3 chained per group
    assert len(seen) == 2 * 4
    assert not r.pending


# ------------------------------------------------------- future semantics
def test_future_wait_timeout_then_resolution():
    f = api.Future()
    with pytest.raises(TimeoutError):
        f.wait(timeout=0.05)
    threading.Timer(0.05, lambda: f.set_result(42)).start()
    assert f.wait(timeout=5.0) == 42
    # late callback registration fires immediately
    fired = []
    f.add_done_callback(lambda fut: fired.append(fut.result()))
    assert fired == [42]


def test_error_propagates_and_poisons_dependents():
    r, specs, _ = make_router(n_groups=1, duration=0.0)
    spec = specs[0]
    bad = api.make_op(spec, api.Op.FORWARD, 0, fail=True)
    dep = api.make_op(spec, api.Op.FORWARD, 1, prerequisites=(bad.req_id,))
    grand = api.make_op(spec, api.Op.FORWARD, 2, prerequisites=(dep.req_id,))
    f_bad = r.submit_queued_operation(bad)
    f_dep = r.submit_queued_operation(dep)
    f_grand = r.submit_queued_operation(grand)
    r.run_until_idle(timeout=30.0)    # must terminate despite the failure
    with pytest.raises(RuntimeError, match="injected"):
        f_bad.wait(timeout=1.0)
    with pytest.raises(RuntimeError, match="prerequisite"):
        f_dep.result()
    with pytest.raises(RuntimeError, match="prerequisite"):
        f_grand.result()
    assert not r.pending
    states = {t.state for t in r.executor.tasks.values()}
    assert states == {State.FAILED}


def test_timeout_bounds_call_even_with_hung_op():
    """An op stuck inside execute cannot be interrupted, but the timeout
    must still bound run_until_idle (the worker is abandoned after a short
    grace) instead of spinning on join forever."""
    r, specs, _ = make_router(n_groups=1, duration=3.0)
    r.submit_queued_operation(api.make_op(specs[0], api.Op.FORWARD, 0))
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="stuck"):
        r.run_until_idle(timeout=0.2)
    assert time.monotonic() - t0 < 2.5   # 0.2s deadline + 1s grace + slack


def test_abandoned_worker_is_tracked_not_silently_leaked():
    """Regression: the abandon grace used to drop the hung worker's handle
    on the floor — the thread leaked invisibly and nothing ever reported
    it. Now run_until_idle records it, abandoned_workers() names it while
    the hung op runs, and the entry self-prunes once the op returns."""
    r, specs, _ = make_router(n_groups=1, duration=2.0)
    r.submit_queued_operation(api.make_op(specs[0], api.Op.FORWARD, 0))
    with pytest.raises(TimeoutError, match="stuck"):
        r.run_until_idle(timeout=0.2)
    names = r.abandoned_workers()
    assert names and names[0].startswith("dispatch-g0")
    # once the stuck execute returns, the daemon exits and the report drains
    deadline = time.monotonic() + 10.0
    while r.abandoned_workers() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert r.abandoned_workers() == []


def test_serial_driver_also_poisons_dependents():
    r, specs, _ = make_router(n_groups=1, duration=0.0)
    spec = specs[0]
    bad = api.make_op(spec, api.Op.FORWARD, 0, fail=True)
    dep = api.make_op(spec, api.Op.FORWARD, 1, prerequisites=(bad.req_id,))
    f_bad = r.submit_queued_operation(bad)
    f_dep = r.submit_queued_operation(dep)
    r.drain()
    with pytest.raises(RuntimeError):
        f_bad.result()
    with pytest.raises(RuntimeError, match="prerequisite"):
        f_dep.result()
    assert not r.pending


@pytest.mark.parametrize("driver", ["serial", "run_until_idle", "serve"])
def test_group_serves_on_after_a_failure_poisons_its_dependents(driver):
    """An op that raises fails its dependents, not its group: an
    independent op on the same group then completes, and only it is
    billed."""
    r, specs, _ = make_router(n_groups=1, duration=0.0)
    spec = specs[0]
    wpg = r.wpgs[spec.deployment_id]

    def drive():
        if driver == "serial":
            r.drain()
        elif driver == "run_until_idle":
            r.run_until_idle(timeout=30.0)
        else:
            assert r.wait_idle(timeout=30.0)

    if driver == "serve":
        r.serve()
    try:
        bad = api.make_op(spec, api.Op.FORWARD, 0, fail=True)
        dep = api.make_op(spec, api.Op.FORWARD, 1,
                          prerequisites=(bad.req_id,))
        f_bad = r.submit_queued_operation(bad)
        f_dep = r.submit_queued_operation(dep)
        drive()
        with pytest.raises(RuntimeError, match="injected"):
            f_bad.wait(timeout=5.0)
        with pytest.raises(RuntimeError, match="prerequisite"):
            f_dep.wait(timeout=5.0)
        assert wpg.exec_log == []
        ok = api.make_op(spec, api.Op.FORWARD, 2)
        f_ok = r.submit_queued_operation(ok)
        drive()
        assert f_ok.wait(timeout=5.0) == {"req_id": ok.req_id}
        assert [op for op, _ in wpg.exec_log] == ["forward"]
        assert r.executor.tasks[ok.req_id].state == State.COMPLETED
        assert not r.pending
    finally:
        r.shutdown()


def test_create_deployment_adopts_an_explicit_state_manager():
    """A caller's StateManager becomes the group's, and the WPG is built
    against it."""
    r = Router(wpg_factory=lambda spec, sm: StubWPG(spec, sm, 0.0, []))
    sm = StateManager(node_id="mine")
    spec = api.DeploymentSpec(deployment_id="d", job_id="j",
                              model_name="stub", role="train")
    wpg = r.create_deployment(spec, group_id=3, state_manager=sm)
    assert wpg.sm is sm and r.state_managers[3] is sm
    assert r.group_of["d"] == 3 and r.wpgs["d"] is wpg


def test_teardown_drops_only_the_departing_deployments_state():
    r = Router(wpg_factory=lambda spec, sm: StubWPG(spec, sm, 0.0, []))
    wpgs = {}
    for job in ("stay", "go"):
        spec = api.DeploymentSpec(deployment_id=f"{job}-train", job_id=job,
                                  model_name="stub", role="train")
        wpgs[job] = r.create_deployment(spec, group_id=0)
        wpgs[job].sm.register(wpgs[job].job_prefix,
                              {"w": np.ones((4, 4), np.float32)})
    sm = r.state_managers[0]
    r.teardown("go-train")
    assert not sm.keys_for(wpgs["go"].job_prefix)
    assert sm.keys_for(wpgs["stay"].job_prefix)
    assert "go-train" not in r.wpgs and "go-train" not in r.group_of


@pytest.mark.parametrize("driver", ["serial", "concurrent"])
def test_broken_callback_fails_loudly_at_driver_exit(driver):
    """A user callback that raises must not vanish silently (nor kill a
    dispatch thread mid-protocol): the op's work completes, the error is
    recorded, and the driver raises on exit."""
    r, specs, _ = make_router(n_groups=1, duration=0.0)
    f = r.submit_queued_operation(api.make_op(specs[0], api.Op.FORWARD, 0))
    f.add_done_callback(lambda fut: 1 / 0)
    with pytest.raises(RuntimeError, match="callback"):
        if driver == "serial":
            r.drain()
        else:
            r.run_until_idle(timeout=30.0)
    assert f.result()["req_id"] > 0       # the op itself still completed
    assert len(r.callback_errors) == 1
    assert not r.pending


# --------------------------------------------------------- virtual clock
def _virtual_run(order_jobs):
    clock = VirtualClock()
    trace = []
    router = Router(now=clock, wpg_factory=lambda spec, sm: StubWPG(
        spec, sm, 0.0, trace))
    specs = {}
    for job in ("A", "B"):
        spec = api.DeploymentSpec(deployment_id=f"dep{job}", job_id=job,
                                  model_name="stub", role="train")
        router.create_deployment(spec, group_id=0)   # shared group
        specs[job] = spec
    for job, est in order_jobs:
        router.submit_queued_operation(
            api.make_op(specs[job], api.Op.FORWARD, exec_estimate=est))
        clock.advance(1.0)           # deterministic arrival spacing
    router.drain()
    return [dep for dep, _, _, _ in trace]


def test_hrrs_admission_deterministic_under_virtual_clock():
    """The SAME admission path that drives wall-clock dispatch, replayed on
    a manually-advanced clock, must order identically run-to-run."""
    workload = [("A", 3.0), ("B", 1.0), ("A", 2.0), ("B", 5.0),
                ("A", 1.0), ("B", 2.0)]
    first = _virtual_run(workload)
    second = _virtual_run(workload)
    assert first == second
    assert len(first) == len(workload)


def test_virtual_clock_advances_monotonically():
    clock = VirtualClock(start=5.0)
    assert clock.now() == 5.0
    assert clock.advance(2.5) == 7.5
    assert clock() == 7.5
    with pytest.raises(ValueError):
        clock.advance(-1.0)


# ----------------------------------------------- signal-driven teardown
def _count_cv_waits(executor):
    """Instrument the executor's condition variable: record the timeout of
    every wait() a dispatch worker performs."""
    waits = []
    orig_wait = executor.cv.wait

    def counting_wait(timeout=None):
        waits.append(timeout)
        return orig_wait(timeout)

    executor.cv.wait = counting_wait
    return waits


def test_idle_worker_parks_with_zero_polling_wakeups():
    """An idle dispatcher must block on the cv with NO timeout and NO
    periodic wakeups while another group's op runs (PR 1 polled every
    50 ms here). Wakeups may only come from real notifications."""
    r, specs, _ = make_router(n_groups=2, duration=0.4)
    waits = _count_cv_waits(r.executor)
    # only group 0 gets work; group 1's worker parks for the whole 0.4 s
    r.submit_queued_operation(api.make_op(specs[0], api.Op.FORWARD, 0))
    n = r.run_until_idle(timeout=30.0)
    assert n == 1
    # every wait was untimed (signal-driven), none was a 50 ms guard
    assert waits, "expected the idle group's worker to park on the cv"
    assert all(t is None for t in waits), waits
    # a 0.4 s op under 50 ms polling would have produced ~8 wakeups per
    # parked worker; signal-driven parking wakes only on notifications
    assert len(waits) <= 4, waits


def test_shutdown_token_wakes_parked_worker_promptly():
    """With an op still RUNNING past the deadline, the shutdown token must
    be notified through the cv: parked workers exit immediately and the
    call returns within deadline + grace, well before the op finishes."""
    r, specs, _ = make_router(n_groups=2, duration=2.0)
    # group 0's op out-sleeps the deadline; group 1 parks with no work
    r.submit_queued_operation(api.make_op(specs[0], api.Op.FORWARD, 0))
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="stuck"):
        r.run_until_idle(timeout=0.15)
    elapsed = time.monotonic() - t0
    # bounded by deadline (0.15) + 1 s abandon grace, NOT by the 2 s op
    assert elapsed < 1.8, elapsed
    # the parked (idle-group) worker was woken by the shutdown
    # notification and exited; only the stuck executor thread may linger
    lingering = [t for t in threading.enumerate()
                 if t.name == "dispatch-g1" and t.is_alive()]
    assert not lingering


# ------------------------------------------------------- pending cleanup
@pytest.mark.parametrize("driver", ["serial", "concurrent"])
def test_pending_table_emptied_after_completion(driver):
    r, specs, _ = make_router(n_groups=2, duration=0.0)
    for s in specs:
        submit_batch(r, s, 4)
    assert len(r.pending) == 8
    if driver == "serial":
        r.drain()
    else:
        r.run_until_idle(timeout=30.0)
    assert r.pending == {}
    assert all(not q for q in r.request_queues.values())


# ---------------------------------------------------------------- billing
def test_billing_aggregates_across_split_deployments():
    """A job with split train/rollout deployments is billed for BOTH WPGs,
    and repeated billing passes are incremental (no double counting)."""
    c = PlexCluster(n_groups=1)
    c.billing["j"] = BillingRecord("j")
    c.router.wpgs = {
        "j-train": SimpleNamespace(spec=SimpleNamespace(job_id="j"),
                                   exec_log=[("update_actor", 1.0)]),
        "j-rollout": SimpleNamespace(spec=SimpleNamespace(job_id="j"),
                                     exec_log=[("generate", 2.0)]),
    }
    c.router.switch_log = [
        {"t": 0.0, "group": 0, "to_job": "j", "t_offload": 0.5,
         "t_load": 0.25}]
    c._bill_from_logs()
    rec = c.billing["j"]
    assert rec.busy_seconds == pytest.approx(3.0)     # both deployments
    assert rec.switch_seconds == pytest.approx(0.75)
    c._bill_from_logs()                               # idempotent re-pass
    assert rec.busy_seconds == pytest.approx(3.0)
    assert rec.switch_seconds == pytest.approx(0.75)
    c.router.wpgs["j-train"].exec_log.append(("update_actor", 0.5))
    c.router.switch_log.append(
        {"t": 1.0, "group": 0, "to_job": "j", "t_offload": 0.1,
         "t_load": 0.1})
    c._bill_from_logs()                               # incremental pickup
    assert rec.busy_seconds == pytest.approx(3.5)
    assert rec.switch_seconds == pytest.approx(0.95)
