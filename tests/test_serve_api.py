"""The dataflow client API + persistent serve-mode dispatch plane.

Covers the §4.1/§4.2 redesign:
- chainable futures: ``.then``, ``api.gather``, error propagation through
  transforms into dependent operations (poisoning),
- future-valued op arguments: auto-registered prerequisites + dispatch-time
  value splicing (no manual req_id wiring),
- ``Router.serve()``/``shutdown()``: workers park indefinitely while idle,
  jobs attach to new groups mid-serve, ``teardown`` cancels a departing
  deployment's queued ops and drops its queue,
- the acceptance scenario: GRPO + PPO jobs against ``PlexCluster.serve()``
  where the PPO job attaches AFTER the plane started, completes all steps,
  and is billed — plus ``remove_job`` detaching a long job mid-flight,
- serial ``drain()`` replay of a dataflow-chained workload under a
  VirtualClock staying bit-identical across runs.

Fast tests use the sleep-stub WPGs from test_dispatch; the acceptance test
runs real (tiny) models end-to-end.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import api
from repro.core.cluster import PlexCluster
from repro.core.controller import JobConfig
from repro.core.router import Router
from repro.core.scheduler.executor import State, VirtualClock
from test_dispatch import StubWPG, make_router

TINY = (("num_layers", 2), ("d_model", 32), ("num_heads", 4),
        ("num_kv_heads", 2), ("head_dim", 8), ("d_ff", 64),
        ("vocab_size", 64), ("tie_embeddings", True))


def _serve_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("serve-") and t.is_alive()]


def _deploy(router, dep_id="d0", job_id="j0", group_id=0) -> api.Deployment:
    spec = api.DeploymentSpec(deployment_id=dep_id, job_id=job_id,
                              model_name="stub", role="train")
    return router.deploy(spec, group_id=group_id)


# ----------------------------------------------------------- future algebra
def test_then_chains_and_propagates_errors():
    f = api.Future(sources=(7,))
    g = f.then(lambda x: x + 1).then(lambda x: x * 10)
    assert g.sources == (7,)          # provenance survives chaining
    f.set_result(4)
    assert g.result() == 50

    h = api.Future()
    bad = h.then(lambda x: 1 / x)
    tail = bad.then(lambda x: x + 1)  # never runs: error skips transforms
    h.set_result(0)
    with pytest.raises(ZeroDivisionError):
        tail.result()


def test_gather_joins_results_and_first_error_wins():
    a, b = api.Future(sources=(1,)), api.Future(sources=(2, 3))
    j = api.gather(a, b)
    assert j.sources == (1, 2, 3)
    b.set_result("B")
    assert not j.done()
    a.set_result("A")
    assert j.result() == ["A", "B"]   # argument order, not resolution order

    c, d = api.Future(), api.Future()
    j2 = api.gather(c, d)
    c.set_error(RuntimeError("boom"))
    with pytest.raises(RuntimeError, match="boom"):
        j2.result()
    d.set_result("late")              # late success after error: ignored
    assert api.gather().result() == []


# ------------------------------------------------- dataflow op arguments
def test_future_arg_becomes_prerequisite_and_splices():
    r, _, _ = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    first = dep.forward({"x": 1})
    derived = first.then(lambda res: {"from_first": res["req_id"]})
    second = dep.forward(derived)     # future as argument
    (req2,) = second.sources
    task = r.executor.tasks[req2]
    assert task.prerequisites == first.sources  # auto-registered edge
    r.drain()
    # the spliced value reached the WPG: its qop args held the dict
    assert second.result()["req_id"] == req2
    qop_args = r.executor.tasks[req2]           # task retained for telemetry
    assert qop_args.state == State.COMPLETED


def test_spliced_value_visible_to_execution():
    """The executed op must see the RESOLVED value, not the Future."""
    seen = {}

    class RecordingWPG(StubWPG):
        def execute(self, qop):
            seen[qop.req_id] = qop.args
            return super().execute(qop)

    trace = []
    r = Router(wpg_factory=lambda spec, sm: RecordingWPG(spec, sm, 0.0,
                                                         trace))
    dep = _deploy(r)
    f1 = dep.forward("payload")
    f2 = dep.forward(f1.then(lambda res: ("derived", res["req_id"])))
    r.drain()
    (req2,) = f2.sources
    assert seen[req2] == (("derived", f1.sources[0]),)


def test_deep_nested_future_arg_gets_prereq_and_splices():
    """The prerequisite scan and the dispatch splice must reach the SAME
    depth: a future nested dict->list->list below an argument still gets
    its dependency edge and its value substituted."""
    seen = {}

    class RecordingWPG(StubWPG):
        def execute(self, qop):
            seen[qop.req_id] = qop.args
            return super().execute(qop)

    trace = []
    r = Router(wpg_factory=lambda spec, sm: RecordingWPG(spec, sm, 0.0,
                                                         trace))
    dep = _deploy(r)
    f1 = dep.forward(0)
    f2 = dep.forward({"a": [[f1.then(lambda res: res["req_id"])]]})
    (req2,) = f2.sources
    assert r.executor.tasks[req2].prerequisites == f1.sources
    r.drain()
    assert seen[req2] == ({"a": [[f1.sources[0]]]},)


def test_then_transform_error_poisons_dependent_op():
    """A raising .then transform fails the dependent op (and its own
    dependents), and every driver still terminates."""
    r, _, _ = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    gen = dep.forward(0)
    bad_batch = gen.then(lambda res: 1 / 0)
    upd = dep.update_actor(bad_batch)
    tail = dep.forward(upd)           # transitively poisoned
    r.run_until_idle(timeout=30.0)
    assert gen.result()["req_id"] > 0
    with pytest.raises(ZeroDivisionError):
        bad_batch.result()
    with pytest.raises(ZeroDivisionError):
        upd.result()
    with pytest.raises(RuntimeError, match="prerequisite"):
        tail.result()
    assert not r.pending


def test_sourceless_unresolved_future_arg_rejected():
    """A hand-made unresolved future in op args has nothing to gate on —
    dispatch would stall a group's lock waiting for it — so submission
    refuses it loudly. A RESOLVED one is plain data and splices fine."""
    r, _, _ = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    with pytest.raises(ValueError, match="no source"):
        dep.forward(api.Future())
    with pytest.raises(ValueError, match="no source"):
        dep.forward(0, after=(api.Future(),))
    done = api.Future()
    done.set_result(41)
    ok = dep.forward(done)
    r.drain()
    assert ok.result()["req_id"] > 0


def test_after_edge_orders_without_payload():
    """`after=` is the pure-ordering dataflow edge (async-staleness gate)."""
    r, _, trace = make_router(n_groups=1, duration=0.005)
    dep = api.Deployment(r.deployments["dep0"], r)
    first = dep.forward(0)
    second = dep.forward(1, after=(first,))
    (req2,) = second.sources
    assert r.executor.tasks[req2].prerequisites == first.sources
    r.run_until_idle(timeout=30.0)
    executed = [req_id for _, req_id, _, _ in trace]
    assert executed == [first.sources[0], req2]


# ------------------------------------------------------------ serve plane
def test_serve_admits_work_submitted_while_parked():
    r, _, _ = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    with r:                           # Router is a serve context manager
        assert r.serving
        f1 = dep.forward(0)
        assert f1.wait(timeout=10.0)["req_id"] > 0
        time.sleep(0.05)              # plane fully idle, worker parked
        f2 = dep.forward(1)
        assert f2.wait(timeout=10.0)["req_id"] > 0
    assert not r.serving
    assert not _serve_threads(), "serve workers leaked after shutdown"
    assert r.serve_executed() == 2


def test_attach_new_group_mid_serve_spawns_worker():
    r, _, _ = make_router(n_groups=1, duration=0.0)
    with r:
        assert len(_serve_threads()) == 1
        dep_new = _deploy(r, dep_id="late", job_id="late-job", group_id=5)
        assert len(_serve_threads()) == 2
        assert dep_new.forward(0).wait(timeout=10.0)["req_id"] > 0
    assert not _serve_threads()


def test_teardown_cancels_queued_ops_and_drops_queue():
    # duration keeps the first op RUNNING while the rest queue behind it
    r, _, _ = make_router(n_groups=1, duration=0.15)
    dep = _deploy(r, dep_id="victim", job_id="vjob", group_id=1)
    with r:
        running = dep.forward(0)
        queued = [dep.forward(i) for i in range(1, 4)]
        time.sleep(0.05)              # let the first op start executing
        r.teardown("victim")
        # in-flight op resolves (result), queued ops poison (error)
        assert running.wait(timeout=10.0)["req_id"] > 0
        for q in queued:
            with pytest.raises(RuntimeError, match="torn down"):
                q.wait(timeout=10.0)
        r.wait_idle(timeout=10.0)
    assert "vjob" not in r.request_queues     # queue dropped with the job
    assert not r.pending
    assert all(lock.holder is None for lock in r.executor.locks.values())


def test_teardown_poisons_cross_deployment_dependents():
    r, _, _ = make_router(n_groups=2, duration=0.1)
    dep0 = api.Deployment(r.deployments["dep0"], r)
    victim = _deploy(r, dep_id="victim", job_id="vjob", group_id=1)
    with r:
        blocker = victim.forward(0)   # occupies the victim's group
        vf = victim.forward(1)        # queued behind it
        downstream = dep0.forward(vf) # other deployment depends on it
        time.sleep(0.03)
        r.teardown("victim")
        with pytest.raises(RuntimeError, match="torn down"):
            vf.wait(timeout=10.0)
        with pytest.raises(RuntimeError):
            downstream.wait(timeout=10.0)   # poisoned transitively
        r.wait_idle(timeout=10.0)
        blocker.wait(timeout=10.0)


def test_serial_driver_guarded_while_serving():
    r, _, _ = make_router(n_groups=1, duration=0.0)
    with r:
        with pytest.raises(RuntimeError, match="serve"):
            r.step()
        with pytest.raises(RuntimeError, match="serve"):
            r.run_until_idle()
    r.drain()                         # available again after shutdown


def test_submit_to_torn_down_deployment_raises():
    r, _, _ = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    r.teardown("dep0")
    with pytest.raises(RuntimeError, match="unknown deployment"):
        dep.forward(0)


# ----------------------------------------- VirtualClock dataflow replay
def _virtual_dataflow_run():
    """A GRPO/PPO-shaped chained workload (generate -> transform ->
    future-arg update, interleaved across two jobs) driven by serial
    drain() under a VirtualClock; returns admission order as submission
    ordinals (req_ids differ across runs: the api counter is global)."""
    clock = VirtualClock()
    trace = []
    router = Router(now=clock,
                    wpg_factory=lambda spec, sm: StubWPG(spec, sm, 0.0,
                                                         trace))
    deps = [_deploy(router, dep_id=f"dep{j}", job_id=f"job{j}", group_id=0)
            for j in range(2)]
    ordinal, prev = {}, {0: None, 1: None}
    for step in range(6):
        for j, dep in enumerate(deps):
            gate = (prev[j],) if prev[j] is not None else ()
            gen = dep.generate(np.zeros((2, 4), np.int32),
                               max_new_tokens=4,
                               exec_estimate=0.5 + (step * 5 + j) % 7,
                               after=gate)
            batch = gen.then(lambda res: {"packed": res["req_id"]})
            upd = dep.update_actor(batch,
                                   exec_estimate=1.0 + (step * 3 + j) % 5)
            prev[j] = upd
            ordinal[gen.sources[0]] = len(ordinal)
            ordinal[upd.sources[0]] = len(ordinal)
            clock.advance(0.25)
    router.drain()
    assert not router.pending
    return [ordinal[req_id] for _, req_id, _, _ in trace]


def test_dataflow_chain_replay_bit_identical_under_virtual_clock():
    first = _virtual_dataflow_run()
    second = _virtual_dataflow_run()
    assert len(first) == 2 * 2 * 6    # gen + update, 2 jobs, 6 steps
    assert first == second, "dataflow replay diverged between runs"


# ------------------------------------------------- acceptance: GRPO + PPO
def _tiny_job(job_id, seed, steps=2, staleness=0):
    return JobConfig(job_id=job_id, model_name="qwen2-0.5b", steps=steps,
                     batch_size=4, group_size=2, max_new_tokens=4,
                     seq_len=24, overrides=TINY, seed=seed,
                     async_staleness=staleness)


def test_serve_grpo_then_ppo_attach_complete_and_bill():
    """Acceptance: a GRPO job starts under a live serve() plane; a PPO job
    attaches AFTER serving began (on a NEW group, spawning its dispatch
    worker dynamically); a long third job detaches mid-flight. Both
    surviving jobs complete all steps and are billed."""
    c = PlexCluster(n_groups=1)
    c.add_job(_tiny_job("grpo-job", seed=1, steps=2))
    with c.serve():
        # wait until the pre-registered job makes real progress
        deadline = time.monotonic() + 240
        while not c.controllers["grpo-job"].reward_log:
            assert time.monotonic() < deadline, "grpo job made no progress"
            time.sleep(0.05)
        # NOW attach the PPO job to a brand-new group, mid-serve
        c.add_job(_tiny_job("ppo-job", seed=2, steps=2), group_id=1,
                  algo="ppo")
        # and a long-running job that will be detached mid-flight
        c.add_job(_tiny_job("doomed", seed=3, steps=50), group_id=0)
        deadline = time.monotonic() + 240
        while c.controllers["doomed"].steps_completed < 1:
            assert time.monotonic() < deadline, "doomed job made no progress"
            time.sleep(0.05)
        removed = c.remove_job("doomed")
        assert removed.steps_completed >= 1
    # serve() exit joined the client threads: everything completed
    for job, algo_steps in (("grpo-job", 2), ("ppo-job", 2)):
        ctl = c.controllers[job]
        assert ctl.steps_completed == algo_steps, job
        assert len(ctl.metrics_log) == algo_steps, job
        assert len(ctl.reward_log) == algo_steps, job
        for m in ctl.metrics_log:
            assert not np.isnan(m["loss"]), (job, m)
        rec = c.billing[job]
        assert rec.steps == algo_steps
        assert rec.busy_seconds > 0.0, f"{job} not billed"
    # the detached job was billed for the work it consumed
    rec = c.billing["doomed"]
    assert rec.steps >= 1 and rec.busy_seconds > 0.0
    # PPO actually trained through the split-op chain
    ppo = c.controllers["ppo-job"]
    assert all("pg_loss" in m and "step" in m for m in ppo.metrics_log)
    # plane fully torn down
    assert not _serve_threads()
    assert not c.router.pending


def test_serve_body_exception_detaches_clients_and_stays_clean():
    """An exception in the serve() block must not orphan client threads:
    still-driving jobs detach (their futures poison, billing keeps the
    consumed work), the plane shuts down, the body's exception propagates,
    and a LATER serve session does not resurrect removed/completed jobs."""
    c = PlexCluster(n_groups=1)
    c.add_job(_tiny_job("longjob", seed=4, steps=50))
    with pytest.raises(ValueError, match="user abort"):
        with c.serve():
            deadline = time.monotonic() + 240
            while c.controllers["longjob"].steps_completed < 1:
                assert time.monotonic() < deadline, "job made no progress"
                time.sleep(0.05)
            raise ValueError("user abort")
    assert not _serve_threads()
    assert not [t for t in threading.enumerate()
                if t.name == "client-longjob" and t.is_alive()]
    rec = c.billing["longjob"]
    assert rec.steps >= 1 and rec.busy_seconds > 0.0
    steps_before = c.controllers["longjob"].steps_completed
    with c.serve():                     # removed job must NOT relaunch
        time.sleep(0.2)
    assert c.controllers["longjob"].steps_completed == steps_before
    assert not _serve_threads()


# ------------------------------------- callbacks off the dispatch thread
def _callback_threads(job=None):
    prefix = "callbacks-" if job is None else f"callbacks-{job}"
    return [t for t in threading.enumerate()
            if t.name.startswith(prefix) and t.is_alive()]


def _blocking(entered: threading.Event, release: threading.Event, value):
    """A .then transform that parks until ``release`` (a verifier stall)."""
    def fn(res):
        entered.set()
        assert release.wait(timeout=30.0), "test never released the stall"
        return value
    return fn


def test_one_jobs_callback_stall_is_filled_by_another_jobs_ops():
    """Two jobs on one group under serve(): while job A's pack callback is
    parked, job B's whole GENERATE -> pack -> UPDATE_ACTOR chain runs, and
    A's update stays QUEUED behind its unresolved batch."""
    r, _, trace = make_router(n_groups=0, duration=0.0)
    a, b = (_deploy(r, dep_id=f"d{j}", job_id=j) for j in ("A", "B"))
    entered, release = threading.Event(), threading.Event()
    with r:
        gen_a = a.generate([0], max_new_tokens=1)
        upd_a = a.update_actor(gen_a.then(_blocking(entered, release, "a")))
        assert entered.wait(timeout=10.0)
        gen_b = b.generate([0], max_new_tokens=1)
        upd_b = b.update_actor(gen_b.then(lambda res: "b"))
        assert upd_b.wait(timeout=10.0)["req_id"] == upd_b.sources[0]
        assert not upd_a.done()
        assert r.executor.tasks[upd_a.sources[0]].state == State.QUEUED
        release.set()
        assert upd_a.wait(timeout=10.0)["req_id"] == upd_a.sources[0]
    executed = [req for _, req, _, _ in trace]
    assert executed.index(upd_b.sources[0]) < executed.index(upd_a.sources[0])
    assert r.telemetry.snapshot()["dispatch.admitted_in_callbacks"] >= 1


def test_op_waits_for_its_argument_future_not_only_its_source_op(
        monkeypatch):
    """An update whose GENERATE COMPLETED but whose batch future is still
    being packed stays QUEUED (never RUNNING) and is admitted once the
    batch resolves. The dispatch thread never waits on the future: with
    the splice's bound at a microsecond, a wait would fail the op."""
    monkeypatch.setattr(api, "SPLICE_TIMEOUT_S", 1e-6)
    started = []

    class StartLog(StubWPG):
        def execute(self, qop):
            started.append(qop.req_id)
            return super().execute(qop)

    trace = []
    r = Router(wpg_factory=lambda spec, sm: StartLog(spec, sm, 0.0, trace))
    dep = _deploy(r)
    entered, release = threading.Event(), threading.Event()
    with r:
        gen = dep.generate([0], max_new_tokens=1)
        batch = gen.then(_blocking(entered, release, {"packed": 1}))
        upd = dep.update_actor(batch)
        assert entered.wait(timeout=10.0)
        (g,), (u,) = gen.sources, upd.sources
        assert r.executor.tasks[g].state == State.COMPLETED
        task = r.executor.tasks[u]
        assert task.state == State.QUEUED and task.arg_gates == 1
        assert r.executor.pick_next(0) is None
        assert r.executor.pick_next_full(0) is None
        assert u not in started
        release.set()
        assert upd.wait(timeout=10.0)["req_id"] == u
    assert started == [g, u]
    assert r.telemetry.snapshot()["dispatch.arg_gate_s"] > 0.0


def test_errored_argument_future_fails_its_dependent_under_serve():
    r, _, trace = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    with r:
        gen = dep.generate([0], max_new_tokens=1)
        upd = dep.update_actor(gen.then(lambda res: 1 / 0))
        tail = dep.forward(upd)
        with pytest.raises(ZeroDivisionError):
            upd.wait(timeout=10.0)
        with pytest.raises(RuntimeError, match="prerequisite"):
            tail.wait(timeout=10.0)
        assert r.wait_idle(timeout=10.0)
    assert [req for _, req, _, _ in trace] == list(gen.sources)


def test_one_jobs_callbacks_fire_in_the_order_its_ops_finished():
    """The first callback parks until every op has run, so the others
    queue behind it on the job's callback worker; run_until_idle returns
    only once all have fired."""
    n = 5
    all_ran = threading.Event()

    class CountingStub(StubWPG):
        def execute(self, qop):
            out = super().execute(qop)
            if len(self._trace) == n:
                all_ran.set()
            return out

    trace = []
    r = Router(wpg_factory=lambda spec, sm: CountingStub(spec, sm, 0.0,
                                                         trace))
    dep = _deploy(r)
    fired = []
    futs = [dep.forward(i) for i in range(n)]
    futs[0].add_done_callback(
        lambda f: (all_ran.wait(timeout=30.0), fired.append(f.sources[0])))
    for f in futs[1:]:
        f.add_done_callback(lambda f: fired.append(f.sources[0]))
    assert r.run_until_idle(timeout=30.0) == n
    assert fired == [req for _, req, _, _ in trace]
    assert not _callback_threads()


def test_drivers_stay_busy_while_a_resubmitting_callback_is_pending():
    """run_until_idle and wait_idle must not see the plane idle while a
    callback that will submit more work has yet to fire."""
    r, _, _ = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    go, follow = threading.Event(), []

    def resubmit(f):
        assert go.wait(timeout=30.0)
        follow.append(dep.forward("follow-up"))

    dep.forward(0).add_done_callback(resubmit)
    worker = threading.Thread(target=lambda: follow.append(
        r.run_until_idle(timeout=30.0)))
    worker.start()
    go.set()
    worker.join(timeout=30.0)
    assert not worker.is_alive()
    assert follow[-1] == 2 and follow[0].done()

    entered, release, follow = threading.Event(), threading.Event(), []

    def parked_resubmit(f):
        entered.set()
        assert release.wait(timeout=30.0)
        follow.append(dep.forward("follow-up"))

    with r:
        dep.forward(1).add_done_callback(parked_resubmit)
        assert entered.wait(timeout=10.0)
        assert not r.wait_idle(timeout=0.05)
        release.set()
        assert r.wait_idle(timeout=10.0)
        assert follow[0].done()


def test_callback_error_raises_at_shutdown():
    r, _, _ = make_router(n_groups=1, duration=0.0)
    dep = api.Deployment(r.deployments["dep0"], r)
    r.serve()
    f = dep.forward(0)
    f.add_done_callback(lambda fut: 1 / 0)
    assert f.wait(timeout=10.0)["req_id"] > 0
    with pytest.raises(RuntimeError, match="callback"):
        r.shutdown()
    assert len(r.callback_errors) == 1
    assert not _callback_threads()


def test_no_callback_worker_survives_teardown_or_shutdown():
    r, _, _ = make_router(n_groups=0, duration=0.0)
    deps = {j: _deploy(r, dep_id=f"d{j}", job_id=j) for j in ("cbA", "cbB")}
    with r:
        for dep in deps.values():
            assert dep.forward(0).wait(timeout=10.0)["req_id"] > 0
        assert r.wait_idle(timeout=10.0)
        assert len(_callback_threads("cbA")) == 1
        r.teardown("dcbA")
        assert not _callback_threads("cbA")
        assert len(_callback_threads("cbB")) == 1
    assert not _callback_threads()
    assert r.drain() == 0             # serial again: nothing left behind
