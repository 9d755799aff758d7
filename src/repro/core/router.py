"""Stateless Router: control-plane entry point of the execution service.

§5.1: the Router maps logical deployment ids to WPGs, submits every incoming
operation to the Scheduler for admission, and only then dispatches it. It
owns deployment lifecycle (create / init / teardown) and the automatic
context-switch logic (§5.2.2 ``_handle_job_transition``): when an admitted
operation targets a different job than the one resident on the target group,
offload+load operations are prepended transparently.

Dispatch plane
--------------
All drivers share ONE admission path (HRRS scoring + lock-gated start in
``TaskExecutor``). The plane has a *persistent* serve mode and two bounded
wrappers:

- :meth:`serve` / :meth:`shutdown` — the serviceized runtime: one dispatch
  worker thread per node group parks on the executor's condition variable
  indefinitely while idle and admits work the moment it arrives, so
  independently-arriving jobs multiplex against a continuously running
  service. :meth:`create_deployment` on a new group while serving spawns
  that group's worker dynamically; :meth:`teardown` cancels a departing
  deployment's queued operations (their futures resolve with an error and
  dependents are poisoned) so detach-while-serving terminates cleanly.
- :meth:`run_until_idle` — a bounded session of the same worker loop: the
  workers additionally exit once nothing is queued, running, or firing
  callbacks (batch semantics over the identical admission/execute path).
- :meth:`step` / :meth:`drain` — the serial analogue on the same admission
  path, used for the back-to-back baseline and for deterministic replay
  under a :class:`~repro.core.scheduler.executor.VirtualClock`.

Dataflow arguments: an operation whose arguments embed unresolved
:class:`~repro.core.api.Future`\\ s is held by its auto-registered
prerequisites and by an argument gate on the futures themselves
(``TaskExecutor.open_arg_gate``): it is admitted only once each has
resolved, so the values spliced in at dispatch time
(``QueuedOperation.resolve_args``) are there already and a dispatch thread
never waits on one. Client code chains ops without manual req_id wiring.

Client callbacks: under the threaded drivers (:meth:`serve`,
:meth:`run_until_idle`) a finished op's future is resolved on its job's
callback worker (one FIFO daemon thread per job, ``callbacks-<job>``), so
a slow callback — a controller packing and grading a rollout — holds no
group's dispatch thread: the group admits another job's op meanwhile. Each
job's callbacks fire in the order its ops finished. A queued resolution
counts as in flight until it has fired, so no driver declares the plane
idle while a callback may still submit work. The serial driver fires
callbacks inline, on its own thread.

Failure propagation: an operation that raises resolves its future with the
error, and any queued operation whose prerequisite FAILED is itself failed
("poisoned") instead of waiting forever, so every driver always terminates.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from repro.core import api, telemetry
from repro.core.scheduler import hrrs
from repro.core.scheduler.executor import State, Task, TaskExecutor
from repro.core.state_manager import StateManager, Tier
from repro.core.worker import WorkerProcessGroup
from repro.launch.mesh import DevicePlane

logger = logging.getLogger(__name__)


class _CallbackWorker:
    """One job's future resolutions, fired in the order they were queued
    on a daemon thread of its own. :meth:`stop` lets it fire what it holds,
    then exit."""

    def __init__(self, name: str, fire: Callable[..., None]):
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self.thread = threading.Thread(target=self._run, args=(fire,),
                                       name=name, daemon=True)
        self.thread.start()

    def put(self, item: tuple):
        self._queue.put(item)

    def stop(self):
        self._queue.put(None)

    def _run(self, fire: Callable[..., None]):
        while (item := self._queue.get()) is not None:
            fire(*item)
            # drop the op's result before parking: it may hold a rollout
            del item


class Router:
    def __init__(self, now: Callable[[], float] = time.monotonic,
                 policy: str = "hrrs",
                 wpg_factory: Callable[..., object] = WorkerProcessGroup,
                 device_plane: Optional[DevicePlane] = None,
                 devices_per_group: Optional[int] = None):
        """Every node group's WPGs live in this process and drive its chips
        through the group's mesh slice: one process drives all chips."""
        # the device plane leases each group a disjoint mesh slice; on one
        # default device every group shares the lone slice (legacy view)
        self.device_plane = device_plane or DevicePlane(
            slice_size=devices_per_group)
        self.now = now
        # dispatch workers hung inside wpg.execute past their abandon grace
        # (daemon threads we can't kill) — reported, never silently dropped
        self._abandoned: List[threading.Thread] = []
        self.wpgs: Dict[str, object] = {}
        self.deployments: Dict[str, api.DeploymentSpec] = {}
        self.group_of: Dict[str, int] = {}       # deployment -> node group
        self.state_managers: Dict[int, StateManager] = {}
        # realized migration costs (reshard included), consumed by the
        # PlacementDirector to calibrate the migration-cost floor
        self.migrate_log: List[dict] = []
        # the served path's counters (core/telemetry.py): the executor,
        # the group StateManagers and the client controllers add to it
        self.telemetry = telemetry.Counters(now)
        self.executor = TaskExecutor(now=now, policy=policy,
                                     telemetry=self.telemetry)
        # multi-tenant service layer: per-job tenant binding and HRRS
        # priority weight (rho). Unregistered jobs default to the implicit
        # default tenant at priority 1.0 — the multiplicative identity, so
        # untenanted planes score bit-identically to the pre-tenancy plane.
        self.job_priority: Dict[str, float] = {}
        self.job_tenant: Dict[str, str] = {}
        # set by Cluster when tenancy is wired; tenant_telemetry() merges
        # its accounting snapshot (gpu-seconds, SLO attainment, pending)
        self.tenant_ledger = None
        # per-job queued-op table, keyed by req_id for O(1) finalize
        self.request_queues: Dict[str, Dict[int, api.QueuedOperation]] = {}
        self.pending: Dict[int, api.QueuedOperation] = {}
        self.switch_log: List[dict] = []
        self.wpg_factory = wpg_factory
        # exceptions raised by user callbacks during future resolution; a
        # broken callback must not kill a dispatch or callback thread
        self.callback_errors: List[Tuple[int, BaseException]] = []
        # per-job callback workers while a threaded driver runs (None: the
        # serial driver's inline firing), and, per group, the jobs whose
        # callbacks are firing now (dispatch.admitted_in_callbacks)
        self._cb_workers: Optional[Dict[str, _CallbackWorker]] = None
        self._cb_running: Dict[int, Dict[str, int]] = {}
        # persistent serve-mode plane: one stop token per group worker (so
        # retire_group can tear one down) plus the plane-wide shutdown token
        self._serving = False
        self._serve_stop = threading.Event()
        self._serve_stops: Dict[int, threading.Event] = {}
        self._serve_threads: Dict[int, threading.Thread] = {}
        self._serve_executed: Dict[int, List[int]] = {}
        self._serve_err_start = 0

    # ----------------------------------------------------------- lifecycle
    def _group_sm(self, group_id: int) -> StateManager:
        """The group's StateManager, creating it (and leasing the group's
        mesh slice from the device plane) on first sight. The slice lease
        is what gives the group hardware affinity: every WPG on the group
        reads ``sm.mesh_slice`` for its jit/sharding mesh."""
        sm = self.state_managers.get(group_id)
        if sm is None:
            sl = self.device_plane.slice_for_group(group_id)
            sm = StateManager(node_id=f"group{group_id}",
                              clock=self.now, mesh_slice=sl,
                              telemetry=self.telemetry)
            self.state_managers[group_id] = sm
        elif sm.mesh_slice is None:
            sm.mesh_slice = self.device_plane.slice_for_group(group_id)
        return sm

    def mesh_domains(self) -> Dict[int, int]:
        """group id -> mesh-slice index (the placement layer's domain map:
        a move between different domains pays the cross-mesh reshard)."""
        return self.device_plane.domains()

    def create_deployment(self, spec: api.DeploymentSpec, group_id: int = 0,
                          state_manager: Optional[StateManager] = None):
        """Register a deployment (low level; returns the WPG). While serving,
        a deployment on a group without a dispatch worker spawns one, so
        jobs attach to a live plane without a restart."""
        with self.executor.cv:
            sm = state_manager or self._group_sm(group_id)
            self.state_managers[group_id] = sm
        # built OUTSIDE the cv: a slow model build must not stall the
        # dispatch plane
        wpg = self.wpg_factory(spec, sm)
        with self.executor.cv:
            self.wpgs[spec.deployment_id] = wpg
            self.deployments[spec.deployment_id] = spec
            self.group_of[spec.deployment_id] = group_id
            self.request_queues.setdefault(spec.job_id, {})
            # read under the same lock serve() writes it, so an attach
            # concurrent with serve() either lands in serve's group
            # snapshot or observes _serving and spawns the worker itself
            serving = self._serving
        if serving:
            self._ensure_serve_worker(group_id)
        return wpg

    def deploy(self, spec: api.DeploymentSpec, group_id: int = 0,
               state_manager: Optional[StateManager] = None) -> api.Deployment:
        """Client-facing attach: register the deployment and return its bound
        :class:`~repro.core.api.Deployment` handle (the dataflow API)."""
        self.create_deployment(spec, group_id=group_id,
                               state_manager=state_manager)
        return api.Deployment(spec, self)

    def teardown(self, deployment_id: str):
        """Detach a deployment from the (possibly live) plane.

        Its queued operations are cancelled: each resolves its future with a
        teardown error, and anything depending on them is poisoned through
        the normal failure path. An operation already RUNNING completes and
        resolves its future normally. The job's request queue is dropped
        once its last deployment detaches."""
        cancelled: List[Tuple[api.QueuedOperation, Exception]] = []
        ex = self.executor
        last_of_job = False
        with ex.cv:
            wpg = self.wpgs.pop(deployment_id, None)
            spec = self.deployments.pop(deployment_id, None)
            group = self.group_of.pop(deployment_id, None)
            if spec is not None:
                err = RuntimeError(
                    f"deployment {deployment_id} torn down")
                for qop in list(self.pending.values()):
                    if qop.deployment_id != deployment_id:
                        continue
                    task = self.executor.tasks.get(qop.req_id)
                    if task is None or task.state != State.QUEUED:
                        # RUNNING (possibly admitted but not yet executing):
                        # pin the backend so the op completes normally even
                        # though the wpg table entry is gone
                        qop.pinned_wpg = wpg
                        continue
                    self.executor.finish(task, error=str(err))
                    self._finalize(qop)
                    if not self._defer(group, qop, None, err):
                        cancelled.append((qop, err))
                if not any(s.job_id == spec.job_id
                           for s in self.deployments.values()):
                    last_of_job = True
                    self.request_queues.pop(spec.job_id, None)
                    self.job_priority.pop(spec.job_id, None)
                    self.job_tenant.pop(spec.job_id, None)
            if cancelled:
                # hold the idle guard across the error callbacks below:
                # finish() already dropped the open count, and a callback
                # may resubmit (same protocol as _reap_and_resolve)
                ex.inflight += 1
        if wpg is not None:
            # an op pinned mid-execute still reads this deployment's managed
            # state: let it drain before the entries are dropped (bounded;
            # submits to the torn-down deployment are rejected, so the set
            # of its pending ops can only shrink)
            with ex.cv:
                ex.cv.wait_for(
                    lambda: not any(q.deployment_id == deployment_id
                                    for q in self.pending.values()),
                    timeout=120.0)
            wpg.sm.unregister(wpg.sm.keys_for(wpg.job_prefix))
        if cancelled:
            try:
                for qop, err in cancelled:
                    self._resolve_future(qop, None, err)
            finally:
                with ex.cv:
                    ex.inflight -= 1
                    ex.cv.notify_all()
        if last_of_job:
            # the job's ops are all settled: its worker fires what it
            # holds (the cancellations above among them) and exits
            self._stop_callback_workers(spec.job_id, timeout=120.0)

    # -------------------------------------------------------------- submit
    def register_job_tenant(self, job_id: str, tenant_id: str,
                            priority: float = 1.0):
        """Bind a job to its tenant and HRRS priority weight. Every
        subsequently submitted operation of the job is scored with the
        multiplicative ``priority`` term (1.0 = default tenant, exact
        no-op on the score). Cleared when the job's last deployment
        detaches."""
        with self.executor.cv:
            self.job_tenant[job_id] = tenant_id
            self.job_priority[job_id] = priority

    def submit_queued_operation(self, qop: api.QueuedOperation) -> api.Future:
        """Non-blocking API handler (§5.2.2): wrap + enqueue, return at once.

        Thread-safe: future callbacks submit follow-up operations from
        dispatch worker threads while controllers submit from client
        threads; a live serve plane admits the op the moment its group and
        prerequisites allow. An unresolved future among its arguments gates
        it until that future resolves (``TaskExecutor.open_arg_gate``)."""
        with self.executor.cv:
            if qop.deployment_id not in self.group_of:
                raise RuntimeError(
                    f"unknown deployment {qop.deployment_id!r} "
                    "(never created, or torn down)")
            qop.arrival_time = self.now()
            self.request_queues.setdefault(qop.job_id, {})[qop.req_id] = qop
            req = hrrs.Request(req_id=qop.req_id, job_id=qop.job_id,
                               op=qop.op.value, exec_time=qop.exec_estimate,
                               arrival_time=qop.arrival_time, payload=qop,
                               priority=self.job_priority.get(
                                   qop.job_id, 1.0))
            group = self.group_of[qop.deployment_id]
            unresolved = [f for f in qop.arg_futures if not f.done()]
            self.executor.submit(req, group,
                                 prerequisites=qop.prerequisites,
                                 arg_gates=len(unresolved))
            self.pending[qop.req_id] = qop
            for f in unresolved:
                # fires at once, here, if it resolved since the check
                f.add_done_callback(
                    lambda _f, req_id=qop.req_id:
                        self.executor.open_arg_gate(req_id))
        return qop.future

    # ------------------------------------------------------------ dispatch
    def _handle_job_transition(self, group_id: int, qop: api.QueuedOperation,
                               target_wpg):
        """Automatic context switching: if the group's resident job differs,
        prepend offload(current) + load(target)."""
        sm = self.state_managers[group_id]
        # snapshot the deployment map under the lock: attach/detach may
        # mutate it from other threads while this group switches
        with self.executor.cv:
            resident = [w for d, g in self.group_of.items()
                        if g == group_id and d != qop.deployment_id
                        and (w := self.wpgs.get(d)) is not None
                        and w.spec.job_id != qop.job_id]
        resident = [w for w in resident if w.resident()]
        if resident:
            # a switch as switches are counted: it moved a job off the group
            self.telemetry.add("switch.count")
            with telemetry.span("switch", job=qop.job_id, req=qop.req_id):
                t_off = sum(w.offload(Tier.HOST) for w in resident)
                t_load = target_wpg.ensure_resident()
        else:
            t_off, t_load = 0.0, target_wpg.ensure_resident()
        if resident or t_load > 0:
            with self.executor.cv:
                self.switch_log.append({
                    "t": self.now(), "group": group_id, "to_job": qop.job_id,
                    "t_offload": t_off, "t_load": t_load})
        # feed measured setup costs back into HRRS (per group: concurrent
        # groups switch independently)
        nbytes = sm.job_bytes(target_wpg.job_prefix)
        self.executor.set_setup_costs(group_id,
                                      sm.load_time_estimate(nbytes),
                                      sm.offload_time_estimate(nbytes))

    def _resolve_future(self, qop: api.QueuedOperation, result,
                        err: Optional[BaseException]):
        """Resolve the op's future, firing its client callbacks on this
        thread — the job's callback worker under the threaded drivers, the
        driver's own thread under the serial one — and count the time as
        ``dispatch.callback_s``."""
        t0 = self.now()
        try:
            with telemetry.span("callbacks", job=qop.job_id, req=qop.req_id):
                if err is None:
                    qop.future.set_result(result)
                else:
                    qop.future.set_error(err)
        except Exception as cb_err:  # noqa: BLE001 - user callback bug
            logger.warning("callback for op %d raised: %r",
                           qop.req_id, cb_err)
            self.callback_errors.append((qop.req_id, cb_err))
        self.telemetry.add("dispatch.callback_s", self.now() - t0)

    def _defer(self, group_id: Optional[int], qop: api.QueuedOperation,
               result, err: Optional[BaseException]) -> bool:
        """Queue the op's resolution on its job's callback worker, started
        on first use, and return True; False where no threaded driver runs
        (the caller then resolves inline, outside the lock). Must hold
        executor.cv. The queued resolution counts as in flight until it has
        fired."""
        workers = self._cb_workers
        if workers is None:
            return False
        w = workers.get(qop.job_id)
        if w is None:
            w = workers[qop.job_id] = _CallbackWorker(
                f"callbacks-{qop.job_id}", self._fire_deferred)
        self.executor.inflight += 1
        w.put((group_id, qop, result, err))
        return True

    def _fire_deferred(self, group_id: Optional[int],
                       qop: api.QueuedOperation, result,
                       err: Optional[BaseException]):
        """A callback worker's firing of one queued resolution."""
        ex = self.executor
        with ex.cv:
            jobs = self._cb_running.setdefault(group_id, {})
            jobs[qop.job_id] = jobs.get(qop.job_id, 0) + 1
        try:
            self._resolve_future(qop, result, err)
        finally:
            with ex.cv:
                jobs = self._cb_running[group_id]
                jobs[qop.job_id] -= 1
                if not jobs[qop.job_id]:
                    del jobs[qop.job_id]
                ex.inflight -= 1
                ex.cv.notify_all()

    def _stop_callback_workers(self, job_id: Optional[str] = None,
                               timeout: Optional[float] = None):
        """Stop one job's callback worker, or every one (back to inline
        firing). Each fires what it holds, then exits; one still firing
        after ``timeout`` is abandoned as a daemon and reported."""
        with self.executor.cv:
            if self._cb_workers is None:
                return
            if job_id is None:
                workers = list(self._cb_workers.values())
                self._cb_workers = None
            else:
                w = self._cb_workers.pop(job_id, None)
                workers = [] if w is None else [w]
        deadline = None if timeout is None else time.monotonic() + timeout
        for w in workers:
            w.stop()
        for w in workers:
            if w.thread is threading.current_thread():
                continue              # stopped from its own callback
            w.thread.join(None if deadline is None
                          else max(0.0, deadline - time.monotonic()))
            if w.thread.is_alive():
                with self.executor.cv:
                    self._abandoned.append(w.thread)
                logger.warning("callback worker %s still firing at its "
                               "stop; abandoned as a daemon (see "
                               "abandoned_workers())", w.thread.name)

    def _raise_callback_errors(self, since: int):
        """Drivers fail loudly at exit if any user callback raised during
        the call (matching the pre-concurrent serial loop, where a callback
        exception propagated out of ``step``) — a broken callback means work
        it was about to submit silently never ran."""
        new = self.callback_errors[since:]
        if new:
            req_id, first = new[0]
            raise RuntimeError(
                f"{len(new)} future callback(s) raised during dispatch; "
                f"first: op {req_id} -> {first!r}") from first

    def _finalize(self, qop: api.QueuedOperation):
        """Drop bookkeeping for a finished request (must hold executor.cv).

        O(1): both tables are keyed by req_id — under a deep queue the old
        per-finish list rebuild made finalization O(n) per op."""
        self.pending.pop(qop.req_id, None)
        queue = self.request_queues.get(qop.job_id)
        if queue is not None:
            queue.pop(qop.req_id, None)

    def _reap_poisoned(self) -> List[Tuple[int, api.QueuedOperation,
                                           Exception]]:
        """FAIL every queued task whose prerequisite FAILED (to fixpoint, so
        chains of dependents collapse in one call). Returns the affected
        (group, qop, error) triples; callers fire the futures OUTSIDE the
        lock."""
        out: List[Tuple[int, api.QueuedOperation, Exception]] = []
        with self.executor.cv:
            # fast path: the full-table scan below is only worth paying
            # after a failure EVENT (a FAILED transition, or a submission
            # under an already-failed prereq) — dispatch calls this every
            # loop, and on a long-lived serve plane "scan forever after the
            # first failure" would grow per-op cost with plane lifetime
            if not self.executor.poison_dirty:
                return out
            changed = True
            while changed:
                changed = False
                for t in list(self.executor.tasks.values()):
                    if t.state != State.QUEUED:
                        continue
                    bad = self.executor.failed_prereqs(t)
                    if not bad:
                        continue
                    cause = self.executor.tasks[bad[0]].error
                    err = RuntimeError(
                        f"prerequisite op {bad[0]} failed: {cause}")
                    self.executor.finish(t, error=str(err))
                    qop = self.pending.get(t.request.req_id)
                    if qop is not None:
                        self._finalize(qop)
                        out.append((t.group_id, qop, err))
                    changed = True
            # fixpoint reached under the lock: nothing QUEUED has a failed
            # prereq until the next failure event sets the flag again
            self.executor.poison_dirty = False
        return out

    def _reap_and_resolve(self) -> None:
        """Reap poisoned tasks and fire their error callbacks under the
        inflight guard: reaping decrements the open-task count under the
        lock, but the error callbacks (which may resubmit work) fire outside
        it — without the guard another dispatch worker could observe
        ``outstanding == 0 and inflight == 0`` in that window, declare idle,
        and exit before the callback's resubmission arrives."""
        ex = self.executor
        with ex.cv:
            poisoned = [(qop, err) for g, qop, err in self._reap_poisoned()
                        if not self._defer(g, qop, None, err)]
            if not poisoned:
                return
            ex.inflight += 1
        try:
            for qop, err in poisoned:
                self._resolve_future(qop, None, err)
        finally:
            with ex.cv:
                ex.inflight -= 1
                ex.cv.notify_all()

    def _execute_admitted(self, group_id: int, task: Task) -> None:
        """Run one admitted (RUNNING) operation to completion and resolve its
        future. Shared by the serial driver and the per-group dispatch
        threads. A dispatch thread hands the resolution to the job's
        callback worker and returns to admission at once; the serial driver
        resolves here, OUTSIDE the executor lock, so callbacks may submit
        follow-up operations."""
        with self.executor.cv:
            qop = self.pending[task.request.req_id]
            # an op RUNNING when its deployment tore down keeps executing on
            # the pinned backend, so it still completes (and bills) normally
            wpg = self.wpgs.get(qop.deployment_id) or qop.pinned_wpg
        result, err = None, None
        try:
            # dataflow splice: substitute resolved values for future args
            # (the argument gate admitted the op once they had resolved)
            qop.resolve_args()
            if wpg is None:
                raise RuntimeError(
                    f"deployment {qop.deployment_id} torn down")
            if qop.op not in (api.Op.INIT,):
                self._handle_job_transition(group_id, qop, wpg)
            result = self._execute_timed(qop, wpg)
        except Exception as e:  # noqa: BLE001 - surface via future
            err = e
        with self.executor.cv:
            self.executor.finish(task, error=None if err is None
                                 else str(err))
            self._finalize(qop)
            deferred = self._defer(group_id, qop, result, err)
        if not deferred:
            self._resolve_future(qop, result, err)

    def _execute_timed(self, qop: api.QueuedOperation, wpg):
        """``wpg.execute`` in a ``plexrl.op.<OP>`` span, counted as
        ``op.<OP>.count``, ``op.<OP>.host_s`` (the call) and
        ``op.<OP>.ready_s`` (the call until its results are ready)."""
        name, tel = qop.op.name, self.telemetry
        # the trace's encoding of span stats splits on commas
        cause = " ".join(str(r) for r in qop.prerequisites)
        t0 = self.now()
        with telemetry.span(f"op.{name}", job=qop.job_id, req=qop.req_id,
                            cause=cause):
            result = wpg.execute(qop)
        tel.add(f"op.{name}.count")
        tel.add(f"op.{name}.host_s", self.now() - t0)
        tel.when_ready(result,
                       lambda t: tel.add(f"op.{name}.ready_s", t - t0))
        return result

    # ------------------------------------------------------ serial driver
    def step(self, max_ops: int = 1) -> int:
        """Serial driver on the shared admission path: admit + execute up to
        ``max_ops`` operations inline (the back-to-back baseline, and the
        deterministic path under a virtual clock)."""
        if self._serving:
            raise RuntimeError("serial driver unavailable while serve() "
                               "workers own the plane; shutdown() first")
        err_start = len(self.callback_errors)
        executed = 0
        for _ in range(max_ops):
            progressed = False
            for group_id in sorted(set(self.group_of.values())):
                self._reap_and_resolve()
                with self.executor.cv:
                    task = self.executor.pick_next(group_id)
                    started = (task is not None
                               and self.executor.try_start(task))
                if not started:
                    continue
                self._execute_admitted(group_id, task)
                executed += 1
                progressed = True
            if not progressed:
                break
        self._raise_callback_errors(err_start)
        return executed

    def drain(self, max_steps: int = 100_000) -> int:
        total = 0
        for _ in range(max_steps):
            n = self.step()
            if n == 0:
                break
            total += n
        return total

    # ------------------------------------------------ shared worker loop
    def _worker_loop(self, group_id: int, stop: threading.Event,
                     persistent: bool, executed: List[int], slot: int,
                     deadline: Optional[float] = None):
        """One node group's dispatch worker. Fully signal-driven: the ONLY
        blocking point is an untimed wait on the executor's condition
        variable; every state change that could unblock it notifies —
        submit, finish, inflight decrement, idle detection, and the stop
        token — so an idle dispatcher performs zero wakeups between
        submissions.

        ``persistent`` workers (serve mode) park on the cv when the plane
        is idle; bounded workers (run_until_idle) exit instead."""
        ex = self.executor
        while not stop.is_set():
            self._reap_and_resolve()
            task = None
            with ex.cv:
                if stop.is_set():
                    return
                t = ex.pick_next(group_id)
                if t is not None and ex.try_start(t):
                    ex.inflight += 1
                    task = t
                    if any(j != t.request.job_id
                           for j in self._cb_running.get(group_id, ())):
                        # the verifier stall of another job, filled
                        self.telemetry.add("dispatch.admitted_in_callbacks")
                elif (not persistent and ex.outstanding() == 0
                        and ex.inflight == 0):
                    ex.cv.notify_all()
                    return
                else:
                    ex.cv.wait()
                    # woken by a notification: re-run the reap (the wakeup
                    # may have been a FAILED finish) and re-check
                    # stop/idle/admission from the loop top
                    continue
            try:
                self._execute_admitted(group_id, task)
                executed[slot] += 1
            finally:
                with ex.cv:
                    ex.inflight -= 1
                    ex.cv.notify_all()
            if deadline is not None and time.monotonic() > deadline:
                stop.set()
                with ex.cv:
                    ex.cv.notify_all()

    # ------------------------------------------------------- serve plane
    def _ensure_serve_worker(self, group_id: int):
        with self.executor.cv:
            # re-check under the lock: an attach that observed a live plane
            # may race shutdown(); spawning against the already-set stop
            # token would register a dead worker
            if not self._serving or self._serve_stop.is_set():
                return
            if group_id in self._serve_threads:
                return
            counter = self._serve_executed.setdefault(group_id, [0])
            stop = self._serve_stops[group_id] = threading.Event()
            t = threading.Thread(
                target=self._worker_loop,
                args=(group_id, stop, True, counter, 0),
                name=f"serve-g{group_id}", daemon=True)
            self._serve_threads[group_id] = t
        t.start()

    def serve(self):
        """Start the persistent dispatch plane: one parked worker per known
        node group, new groups joining dynamically via
        :meth:`create_deployment`. Returns immediately; pair with
        :meth:`shutdown` (or use as a context manager)."""
        with self.executor.cv:
            if self._serving:
                raise RuntimeError("already serving")
            self._serve_stop = threading.Event()
            self._serve_stops = {}
            self._serve_threads = {}
            self._serve_executed = {}
            self._serve_err_start = len(self.callback_errors)
            self._serving = True
            self._cb_workers = {}
            groups = sorted(set(self.group_of.values()))
        for g in groups:
            self._ensure_serve_worker(g)

    def shutdown(self, timeout: Optional[float] = None):
        """Stop the serve plane: parked workers exit immediately; a worker
        mid-execute finishes its operation first (bounded by ``timeout`` if
        given, after which it is abandoned as a daemon). The callback
        workers then fire what they hold and exit. Raises at the end if any
        user callback raised while serving."""
        if not self._serving:
            return
        self._serve_stop.set()
        with self.executor.cv:
            for stop in self._serve_stops.values():
                stop.set()
            self.executor.cv.notify_all()
        deadline = None if timeout is None else time.monotonic() + timeout
        for t in self._serve_threads.values():
            t.join(timeout=None if deadline is None
                   else max(0.0, deadline - time.monotonic()))
        leaked = [t for t in self._serve_threads.values() if t.is_alive()]
        self._stop_callback_workers(
            timeout=None if deadline is None
            else max(0.0, deadline - time.monotonic()))
        with self.executor.cv:
            self._serving = False
            self._serve_threads = {}
            self._serve_stops = {}
            self._abandoned.extend(leaked)
        for t in leaked:
            logger.warning(
                "serve worker %s still hung in execute at shutdown; "
                "abandoned as a daemon (see abandoned_workers())", t.name)
        self._raise_callback_errors(self._serve_err_start)

    def __enter__(self) -> "Router":
        self.serve()
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    @property
    def serving(self) -> bool:
        return self._serving

    def serve_executed(self) -> int:
        """Operations executed by the current/last serve plane."""
        return sum(c[0] for c in self._serve_executed.values())

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until nothing is queued, running, or firing callbacks.
        Usable from any client thread against a live serve plane.
        Returns True once the plane quiesced, False if ``timeout`` elapsed
        first (the caller distinguishes quiesced from timed-out)."""
        ex = self.executor
        with ex.cv:
            return ex.cv.wait_for(
                lambda: ex.outstanding() == 0 and ex.inflight == 0, timeout)

    def abandoned_workers(self) -> List[str]:
        """Names of dispatch workers abandoned while hung in ``execute``
        (bounded drivers give up after their grace; the threads are daemons
        and exit when their op finally returns — entries self-prune here)."""
        with self.executor.cv:
            self._abandoned = [t for t in self._abandoned if t.is_alive()]
            return [t.name for t in self._abandoned]

    # ------------------------------------------- group lifecycle / telemetry
    def known_groups(self) -> List[int]:
        with self.executor.cv:
            return sorted(set(self.group_of.values())
                          | set(self.state_managers)
                          | set(self._serve_threads))

    def ensure_group(self, group_id: int) -> StateManager:
        """Register a node group with the control plane (capacity-adjustment
        spawn, §4.4): its StateManager exists from here on, and while serving
        a dispatch worker is spawned so deployments placed on it are admitted
        the moment they arrive."""
        with self.executor.cv:
            sm = self._group_sm(group_id)
            serving = self._serving
        if serving:
            self._ensure_serve_worker(group_id)
        return sm

    def retire_group(self, group_id: int, timeout: float = 30.0):
        """Capacity-adjustment retire: tear down one group's dispatch worker
        (symmetric to the dynamic spawn in :meth:`create_deployment`) and
        forget its scheduling state. Refuses while the group still hosts
        deployments or open tasks."""
        ex = self.executor
        with ex.cv:
            live = [d for d, g in self.group_of.items() if g == group_id]
            if live:
                raise RuntimeError(
                    f"group {group_id} still hosts deployments {live}")
            stuck = [t.request.req_id for t in ex.tasks.values()
                     if t.group_id == group_id
                     and t.state in (State.QUEUED, State.RUNNING)]
            if stuck:
                raise RuntimeError(
                    f"group {group_id} still has open tasks {stuck}")
            t = self._serve_threads.pop(group_id, None)
            stop = self._serve_stops.pop(group_id, None)
            if stop is not None:
                stop.set()
            ex.cv.notify_all()
        if t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                raise RuntimeError(
                    f"group {group_id} worker did not exit in {timeout}s")
        try:
            ex.drop_group(group_id)
        except RuntimeError:
            # an attach raced the teardown and submitted work: put the
            # dispatch worker back so the new deployment is not stranded
            if self._serving:
                self._ensure_serve_worker(group_id)
            raise
        with ex.cv:
            # re-check under the lock: an attach that raced past drop_group
            # owns the group again — leave its (empty) StateManager alone
            if not any(g == group_id for g in self.group_of.values()):
                sm = self.state_managers.get(group_id)
                if sm is not None and not sm.entries:
                    del self.state_managers[group_id]
                    # return the group's mesh-slice lease to the plane
                    self.device_plane.release(group_id)

    def group_telemetry(self) -> Dict[int, dict]:
        """Per-group queue-depth / occupancy snapshot (the §4.4 capacity
        adjuster's input). Keys: queue_depth (QUEUED ops), running (op
        currently holding the group lock), busy_seconds (cumulative measured
        execution), resident_job, deployments, worker (live serve thread)."""
        ex = self.executor
        with ex.cv:
            groups = (set(self.group_of.values()) | set(self.state_managers)
                      | set(self._serve_threads))
            out: Dict[int, dict] = {}
            for g in sorted(groups):
                lock = ex.locks.get(g)
                out[g] = {
                    "queue_depth": ex.queued_count.get(g, 0),
                    "running": bool(lock and lock.holder is not None),
                    "busy_seconds": ex.group_busy.get(g, 0.0),
                    "resident_job": ex.resident_job.get(g),
                    "deployments": sorted(
                        d for d, gg in self.group_of.items() if gg == g),
                    "worker": g in self._serve_threads,
                }
        return out

    def tenant_telemetry(self) -> Dict[str, dict]:
        """Per-tenant service snapshot alongside :meth:`group_telemetry`.

        Plane-derived keys (always present): queue_depth (QUEUED ops across
        the tenant's jobs), running (ops currently executing), jobs, groups
        (distinct node groups hosting the tenant's deployments). When a
        :class:`~repro.core.tenancy.TenantLedger` is wired (Cluster does),
        its accounting snapshot is merged in: gpu_seconds, steps_total,
        slo_attainment, step_p95_s, pending_jobs."""
        ex = self.executor
        out: Dict[str, dict] = {}

        def slot(tenant: str) -> dict:
            return out.setdefault(tenant, {
                "queue_depth": 0, "running": 0,
                "jobs": set(), "groups": set()})

        with ex.cv:
            for t in ex.tasks.values():
                if t.state not in (State.QUEUED, State.RUNNING):
                    continue
                tenant = self.job_tenant.get(t.request.job_id, "default")
                s = slot(tenant)
                if t.state == State.QUEUED:
                    s["queue_depth"] += 1
                else:
                    s["running"] += 1
            for dep_id, spec in self.deployments.items():
                tenant = self.job_tenant.get(spec.job_id, "default")
                s = slot(tenant)
                s["jobs"].add(spec.job_id)
                s["groups"].add(self.group_of[dep_id])
            for job_id, tenant in self.job_tenant.items():
                slot(tenant)["jobs"].add(job_id)
        ledger = self.tenant_ledger
        if ledger is not None:
            for tenant, acct in ledger.snapshot().items():
                slot(tenant).update(acct)
        for s in out.values():
            s["jobs"] = sorted(s["jobs"])
            s["groups"] = sorted(s["groups"])
        return out

    # ------------------------------------------------- elastic re-placement
    def migrate_job(self, job_id: str, src_group: int, dst_group: int) -> int:
        """Move a job's managed state across groups (paper §4.5.3). Callers
        quiesce + admission-hold the job first (see :meth:`reassign_job`).
        The bulk byte copy runs OUTSIDE the executor lock — a multi-GB
        migration must not stall dispatch on every other group — which is
        safe because the held job's entries are not unregistered by anyone
        (a concurrent switch may at worst offload them tier-wise, and
        ``StateManager.migrate`` reads either tier consistently); only the
        map swaps (wpg.sm, group_of, resident flag) take the lock.

        The realized cost (reshard included, measured via ``self.now``) is
        appended to :attr:`migrate_log`, which the PlacementDirector drains
        to calibrate its migration-cost floors (same-mesh vs cross-mesh)."""
        with self.executor.cv:
            src = self.state_managers[src_group]
            dst = self._group_sm(dst_group)
            targets = [(d, w) for d, w in self.wpgs.items()
                       if w.spec.job_id == job_id]
        t0 = self.now()
        moved = 0
        cross = False
        for _, wpg in targets:
            moved += src.migrate(wpg.job_prefix, dst)
            if src.last_migrate is not None:
                cross = cross or bool(src.last_migrate.get("cross_mesh"))
        dt = self.now() - t0
        with self.executor.cv:
            for dep_id, wpg in targets:
                wpg.sm = dst
                self.group_of[dep_id] = dst_group
            if self.executor.resident_job.get(src_group) == job_id:
                self.executor.resident_job[src_group] = None
            self.migrate_log.append({
                "job": job_id, "src": src_group, "dst": dst_group,
                "bytes": moved, "seconds": dt, "cross_mesh": cross,
                "t": self.now()})
            if len(self.migrate_log) > 1024:
                del self.migrate_log[:len(self.migrate_log) - 1024]
        return moved

    def reassign_job(self, job_id: str, dst_group: int,
                     timeout: float = 120.0) -> int:
        """Realize a re-placement decision against the live plane: hold the
        job's admissions, wait for its RUNNING ops to drain, migrate managed
        state, re-home its queued ops onto the destination group, release.
        Billing continuity is free — exec logs live on the WPGs (which
        survive) and the billing cursors are keyed by deployment id."""
        ex = self.executor
        ex.hold_job(job_id)
        try:
            with ex.cv:
                ok = ex.cv.wait_for(lambda: not ex.job_running(job_id),
                                    timeout)
            if not ok:
                raise TimeoutError(
                    f"job {job_id} did not quiesce within {timeout}s")
            with ex.cv:
                src_groups = {g for d, g in self.group_of.items()
                              if self.deployments[d].job_id == job_id}
            moved = 0
            for src in src_groups:
                if src != dst_group:
                    moved += self.migrate_job(job_id, src, dst_group)
            if self._serving:
                self._ensure_serve_worker(dst_group)
            ex.rehome_job(job_id, dst_group)
        finally:
            ex.release_job(job_id)
        return moved

    def reassign_jobs(self, moves, timeout: float = 120.0) -> List[tuple]:
        """Realize a batched migration plan (the §4.3.2 repack loop's
        output): each move runs through the :meth:`reassign_job`
        hold → drain → migrate → rehome path, in *dependency order* —
        a move INTO a group is executed after moves OUT of it
        (vacate-before-fill), so a swap never transiently double-books a
        destination. A cyclic batch (pure swap) is broken deterministically
        at the lowest job id; group residency is time-multiplexed, so the
        one overlapping tenancy that creates is safe.

        A failing move is captured in its result slot and the remaining
        moves still execute: the plan is realized partially, but every
        executed move is complete and consistent (the caller rolls the
        failed job's *placement* back). Returns ``(move, moved_bytes,
        error)`` tuples in execution order; ``moves`` may be any objects
        with ``job_id`` / ``src_group`` / ``dst_group`` attributes (e.g.
        :class:`~repro.core.scheduler.placement.JobMove`)."""
        remaining = sorted(moves, key=lambda m: m.job_id)
        ordered = []
        while remaining:
            pick = None
            for m in remaining:
                if not any(o.src_group == m.dst_group
                           for o in remaining if o is not m):
                    pick = m
                    break
            if pick is None:           # cycle: every dst is someone's src
                pick = remaining[0]
            remaining.remove(pick)
            ordered.append(pick)
        results: List[tuple] = []
        for m in ordered:
            try:
                moved = self.reassign_job(m.job_id, m.dst_group,
                                          timeout=timeout)
                results.append((m, moved, None))
            except Exception as e:  # noqa: BLE001 - per-move isolation
                results.append((m, 0, e))
        return results

    # -------------------------------------------------- bounded driver
    def run_until_idle(self, timeout: Optional[float] = None) -> int:
        """A bounded session of the dispatch plane: the same per-group
        worker loop as :meth:`serve`, but workers exit once no operation
        is queued, running, or firing callbacks. Returns the number of
        operations executed.

        ``timeout`` (wall-clock seconds) bounds the whole call; on expiry a
        ``TimeoutError`` is raised with the stuck operations listed. A worker
        blocked INSIDE ``wpg.execute`` cannot be interrupted — after a 1 s
        grace it is abandoned as a daemon thread so the bound still holds.
        """
        if self._serving:
            raise RuntimeError("run_until_idle unavailable while serve() "
                               "workers own the plane; shutdown() first")
        groups = sorted(set(self.group_of.values()))
        if not groups:
            return 0
        err_start = len(self.callback_errors)
        deadline = None if timeout is None else time.monotonic() + timeout
        executed = [0] * len(groups)
        timed_out = threading.Event()
        ex = self.executor
        with ex.cv:
            self._cb_workers = {}

        threads = [threading.Thread(
            target=self._worker_loop,
            args=(g, timed_out, False, executed, i, deadline),
            name=f"dispatch-g{g}", daemon=True)
            for i, g in enumerate(groups)]
        for t in threads:
            t.start()
        for t in threads:
            while t.is_alive():
                if deadline is None:
                    t.join()
                    continue
                remaining = deadline - time.monotonic()
                if remaining > 0 and not timed_out.is_set():
                    # sleep exactly until the deadline (or thread exit) —
                    # workers park on the cv and need no supervision
                    t.join(timeout=remaining)
                    continue
                if not timed_out.is_set():
                    timed_out.set()
                    with ex.cv:
                        ex.cv.notify_all()
                # shutdown signalled: workers parked on the cv exit
                # immediately; one stuck INSIDE wpg.execute (threads cannot
                # be killed) gets a 1 s grace, then is abandoned (daemon) so
                # the timeout still bounds this call — reported below
                t.join(timeout=max(0.0, deadline + 1.0 - time.monotonic()))
                if t.is_alive():
                    # the abandon used to drop the handle on the floor: a
                    # WPG hung in execute leaked its worker invisibly, and
                    # shutdown() had nothing to report. Track it.
                    with ex.cv:
                        self._abandoned.append(t)
                    logger.warning(
                        "dispatch worker %s hung in execute past the "
                        "abandon grace; leaked as a daemon (see "
                        "abandoned_workers())", t.name)
                break
        self._stop_callback_workers(
            timeout=None if deadline is None
            else max(0.0, deadline + 1.0 - time.monotonic()))
        if timed_out.is_set():
            with ex.cv:
                stuck = [t.request.req_id for t in ex.tasks.values()
                         if t.state in (State.QUEUED, State.RUNNING)]
            # the deadline may have lapsed while the LAST op was finishing;
            # only a run that left work behind is an actual timeout
            if stuck:
                raise TimeoutError(
                    f"run_until_idle exceeded {timeout}s; "
                    f"stuck ops: {stuck}")
        self._raise_callback_errors(err_start)
        return sum(executed)
