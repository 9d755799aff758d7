"""One run of a served GRPO cell: set-up, the measured window, the check.

The system under test is PlexRL's served path as a user drives it:
``PlexCluster.serve()`` with one ``RLControllerGRPO`` client thread per job
on one node group (Router, HRRS admission, StateManager, WPG). The
benchmark only wraps the program's objects from here, to time and trace
the calls into each layer and to keep what the correctness check needs:

- each controller's ``submit_step`` (a step starts) and
  ``_record_metrics`` (its UPDATE_ACTOR returned: the step ends);
- its ``_pack``, where the verifier runs today: after the controller packs
  the rollouts, the traffic's verifier latency is spent there and its
  graded outcomes set the advantages (random weights never solve a
  problem, so real rewards would all be 0 and every gradient with them);
- each WPG's ``execute`` and the Router's ``_handle_job_transition``, as
  trace spans (``bench.<OP>``, ``bench.switch``). In a traced run a span
  goes on, on a watcher thread of its own, until the call's device work
  (the op's outputs, the switched-in state) is ready; the caller's own
  thread is never held, so the traced program runs as the timed one does;
- each job's initial weights, checked bit for bit against the
  benchmark's own draw from the same seed (``init_mismatch``);
- during set-up only, every context switch's round trip is checked bit
  for bit (``switch_mismatch``).

Set-up runs every job through the traffic's first three steps (the steps
the reference follows), which covers every shape the window uses. The
window then lasts ``--seconds``; each step is credited with the share of
its own duration inside it, and steps in flight at its close finish
untimed, so that share is known.
"""
from __future__ import annotations

import gc
import os
import shutil
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import correctness, flops
from bench import traffic as traffic_lib
from bench.reference import grpo as ref_grpo
from bench.trace_reduce import Trace, find_xplane

WARMUP_STEPS = correctness.REFERENCE_STEPS
SETUP_TIMEOUT_S = 900.0


class CompileCounter:
    """Counts program compilations that the persistent cache did not serve,
    while ``armed``."""

    def __init__(self):
        self.armed = False
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if self.armed and name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_):
        if self.armed and name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def uncached(self) -> int:
        return self.compiles - self.cache_hits


_COUNTER: List[CompileCounter] = []


def compile_counter() -> CompileCounter:
    """The process's one counter (JAX's listeners are process-wide)."""
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


class JobRec:
    """What the benchmark keeps of one job."""

    def __init__(self, plan: traffic_lib.JobPlan):
        self.plan = plan
        self.steps: List[list] = []          # [t_submit, t_end or None]
        self.batches: Dict[int, dict] = {}
        self.losses: Dict[int, float] = {}
        self.grad_norms: Optional[list] = None
        self.delta_norms: Optional[list] = None
        self.init_equal: Optional[tuple] = None
        self.ctl = None
        self.wpg = None

    @property
    def current(self) -> int:
        return len(self.steps)

    def done(self) -> int:
        return sum(1 for s in self.steps if s[1] is not None)

    def credit(self, t0: float, t1: float) -> float:
        return sum((min(e, t1) - max(s, t0)) / (e - s)
                   for s, e in self.steps
                   if e is not None and e > t0 and s < t1 and e > s)


@jax.jit
def _checksums(tree):
    def one(x):
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        bits = jax.lax.bitcast_convert_type(x, width).astype(jnp.uint32).ravel()
        w = jnp.arange(bits.size, dtype=jnp.uint32) % jnp.uint32(65521) + 1
        return jnp.stack([bits.sum(dtype=jnp.uint32),
                          (bits * w).sum(dtype=jnp.uint32)])
    return jnp.stack([one(x) for x in jax.tree.leaves(tree)])


@jax.jit
def _equal_leaves(a, b):
    return jnp.stack([jnp.all(x == y) for x, y in
                      zip(jax.tree.leaves(a), jax.tree.leaves(b))])


class Run:
    """One run of a cell. ``cfg`` and ``traffic`` are the cell's parsed
    configuration and traffic files."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 chips: int = 1, trace_dir: Optional[str] = None,
                 t_start: Optional[float] = None):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds, self.chips = seed, seconds, chips
        self.trace_dir = trace_dir
        self.t_start = time.monotonic() if t_start is None else t_start
        self.reference = correctness.Reference(cfg, traffic)
        self.jobs = {f"job{p.index}": JobRec(p)
                     for p in traffic_lib.plan(traffic, seed)}
        self.stop = threading.Event()
        self.cv = threading.Condition()
        self.setup_phase = True
        self.window: Optional[tuple] = None
        self.switch_checked = 0
        self.switch_mismatch = 0
        self._saved_sums: Dict[str, np.ndarray] = {}
        self._watchers: List[threading.Thread] = []
        self.counter = compile_counter()
        if float(traffic["temperature"]) != 1.0:
            raise ValueError("the GRPO controller samples at temperature 1.0; "
                             "a traffic file cannot ask for another")

    @property
    def tracing(self) -> bool:
        return self.trace_dir is not None

    def _watch(self, name: str, tree):
        """In a traced run, a span ``bench.<name>`` from now until the
        arrays of ``tree`` are ready, on a thread of its own."""
        arrays = [x for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)]
        if not self.tracing or not arrays:
            return

        def wait():
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                try:
                    jax.block_until_ready(arrays)
                except RuntimeError:    # donated to a later op: ready then
                    pass

        t = threading.Thread(target=wait, name=f"bench-{name}", daemon=True)
        t.start()
        self._watchers.append(t)

    # ----------------------------------------------------------- program
    def _make_wpg(self, spec, sm):
        from repro.core.worker import WorkerProcessGroup
        from repro.rl.grpo import GRPOConfig
        from repro.train.optimizer import AdamWConfig

        job = self.jobs[spec.job_id]
        wpg = WorkerProcessGroup(
            spec, sm, rng_seed=job.plan.seed,
            grpo_cfg=GRPOConfig(clip_eps=self.traffic["grpo"]["clip_eps"]),
            adamw_cfg=AdamWConfig(**self.traffic["optimizer"]))
        execute = wpg.execute

        def traced_execute(qop):
            with jax.profiler.TraceAnnotation(f"bench.{qop.op.name}"):
                out = execute(qop)
            self._watch(qop.op.name, out)
            return out

        wpg.execute = traced_execute
        job.wpg = wpg
        return wpg

    def _job_config(self, job_id: str, job: JobRec):
        from repro.core.controller import JobConfig
        tr = self.traffic
        prog = self.cfg["program"]
        return JobConfig(
            job_id=job_id, model_name=prog["registry"],
            batch_size=job.plan.batch_size, group_size=tr["group_size"],
            prompt_len=tr["prompt_len"], max_new_tokens=tr["max_new_tokens"],
            seq_len=tr["seq_len"], steps=10 ** 9,
            async_staleness=tr["async_staleness"], seed=job.plan.seed,
            overrides=tuple(sorted(prog["overrides"].items())))

    def _attach(self, ctl, job: JobRec):
        """Wrap one controller (see the module docstring)."""
        job.ctl = ctl
        submit_init, submit_step = ctl.submit_init, ctl.submit_step
        pack = ctl._pack
        record, drive = ctl._record_metrics, ctl.drive
        b1 = self.traffic["optimizer"]["b1"]

        def init_checked():
            return submit_init().then(lambda r: (self._check_init(job), r)[1])

        def timed_submit(*args, **kwargs):
            with self.cv:
                job.steps.append([time.monotonic(), None])
            return submit_step(*args, **kwargs)

        def verified_pack(prompts, answers, res, **kwargs):
            k = job.current
            with jax.profiler.TraceAnnotation("bench.pack"):
                batch = pack(prompts, answers, res, **kwargs)
            with jax.profiler.TraceAnnotation("bench.verifier"):
                time.sleep(job.plan.verifier_s(k))
            adv = job.plan.advantages(k)
            if k <= correctness.REFERENCE_STEPS:
                job.batches[k] = dict(
                    tokens=np.asarray(batch["tokens"]),
                    loss_mask=np.asarray(batch["loss_mask"]),
                    advantages=adv,
                    logprobs=np.asarray(res["logprobs"], np.float32),
                    alive=np.asarray(res["alive"]))
            return dict(batch, advantages=jnp.asarray(adv))

        def timed_record(metrics):
            k = job.current
            with self.cv:
                job.steps[k - 1][1] = time.monotonic()
            if k <= correctness.REFERENCE_STEPS:
                job.losses[k] = float(metrics["loss"])
            if k == 1:
                job.grad_norms = [n / (1 - b1) for n in
                                  ref_grpo.leaf_norms(job.wpg.opt_state().mu)]
            if k == correctness.REFERENCE_STEPS:
                job.delta_norms = ref_grpo.diff_norms(
                    job.wpg.params(), self.reference.init_eager(job.plan.seed))
            out = record(metrics)
            with self.cv:
                self.cv.notify_all()
            return out

        def stoppable_drive(stop=None, step_hook=None, **kwargs):
            return drive(stop=self.stop, step_hook=step_hook, **kwargs)

        ctl.submit_init = init_checked
        ctl.submit_step = timed_submit
        ctl._pack = verified_pack
        ctl._record_metrics = timed_record
        ctl.drive = stoppable_drive

    def _check_init(self, job: JobRec):
        """How many of the program's initial leaves equal the benchmark's
        draw from the same seed, bit for bit."""
        mine = job.wpg.params()
        theirs = self.reference.init_eager(job.plan.seed)
        if jax.tree.structure(mine) != jax.tree.structure(theirs) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(mine), jax.tree.leaves(theirs))):
            job.init_equal = (0, len(jax.tree.leaves(theirs)))
            return
        eq = np.asarray(_equal_leaves(mine, theirs))
        job.init_equal = (int(eq.sum()), int(eq.size))

    def _state_sums(self, wpg) -> np.ndarray:
        return np.asarray(_checksums((wpg.params(), wpg.opt_state())))

    def _wrap_switch(self, router):
        transition = router._handle_job_transition

        def checked_transition(group_id, qop, target_wpg):
            check = self.setup_phase
            if check:
                with router.executor.cv:
                    others = [w for w in router.wpgs.values()
                              if w.spec.job_id != qop.job_id]
                for w in others:
                    if w.resident():
                        self._saved_sums[w.job_prefix] = self._state_sums(w)
            with jax.profiler.TraceAnnotation("bench.switch"):
                transition(group_id, qop, target_wpg)
            if self.tracing:
                self._watch("switch", (target_wpg.params(),
                                       target_wpg.opt_state()))
            saved = self._saved_sums.pop(target_wpg.job_prefix, None) \
                if check else None
            if saved is not None:
                self.switch_checked += 1
                same = self._state_sums(target_wpg) == saved
                self.switch_mismatch += int((~same.all(axis=1)).sum())

        router._handle_job_transition = checked_transition

    # -------------------------------------------------------------- run
    def _await_warmup(self, cluster):
        deadline = time.monotonic() + SETUP_TIMEOUT_S
        with self.cv:
            while not all(j.done() >= WARMUP_STEPS for j in self.jobs.values()):
                if cluster.client_errors:
                    job, err = next(iter(cluster.client_errors.items()))
                    raise RuntimeError(f"{job} failed in set-up: {err!r}")
                if time.monotonic() > deadline:
                    raise TimeoutError("set-up did not finish the warm-up steps")
                self.cv.wait(timeout=1.0)

    def _measure(self):
        if self.tracing:
            jax.profiler.start_trace(self.trace_dir)
        self.setup_phase = False
        self.counter.armed = True
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, t0 + self.seconds - time.monotonic()))
        t1 = time.monotonic()
        self.counter.armed = False
        self.window = (t0, t1)
        self.stop.set()

    def _stop_trace(self):
        """Once the steps in flight have finished and every span is closed:
        the spans of calls that straddle the window's close are kept."""
        for t in self._watchers:
            t.join()
        jax.profiler.stop_trace()

    def run(self) -> dict:
        from repro.core.cluster import PlexCluster

        cluster = PlexCluster(n_groups=1, wpg_factory=self._make_wpg)
        self._wrap_switch(cluster.router)
        for job_id, job in self.jobs.items():
            self._attach(cluster.add_job(self._job_config(job_id, job)), job)
        compiles_before = self.counter.uncached
        with cluster.serve():
            self._await_warmup(cluster)
            self._measure()
        if self.tracing:
            self._stop_trace()
        t0, t1 = self.window
        devices = jax.local_devices()[:self.chips]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        # a transition that moved another job's state off the chip; the
        # Router also logs an entry for each op whose own state was resident
        switches = sum(1 for e in cluster.router.switch_log
                       if t0 <= e["t"] <= t1 and e["t_offload"] > 0)
        for job in self.jobs.values():
            job.ctl = job.wpg = None
        del cluster
        gc.collect()
        left = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in devices)
        return dict(window=(t0, t1), setup_s=t0 - self.t_start,
                    bytes_in_use_after=left,
                    memory_peak_bytes=peak, devices=devices,
                    switches_in_window=switches,
                    compiles_in_window=self.counter.uncached - compiles_before)

    # ------------------------------------------------------------ check
    def job_records(self) -> List[dict]:
        out = []
        for job in self.jobs.values():
            if (job.init_equal is None or job.grad_norms is None
                    or job.delta_norms is None
                    or len(job.batches) < correctness.REFERENCE_STEPS):
                raise RuntimeError(f"job {job.plan.index} kept no full record "
                                   "of its first steps")
            out.append(dict(seed=job.plan.seed, batches=job.batches,
                            losses=[job.losses[k] for k in
                                    range(1, correctness.REFERENCE_STEPS + 1)],
                            grad_norms=job.grad_norms,
                            delta_norms=job.delta_norms))
        return out

    def state_numbers(self) -> dict:
        """The exact checks of the run's state: initial weights, and in a
        cell of several jobs every set-up switch's round trip."""
        records = self.job_records()
        nums = {"init_mismatch": float(max(
            job.init_equal[1] - job.init_equal[0]
            for job in self.jobs.values()))}
        if len(records) > 1:
            nums["switch_mismatch"] = float(
                self.switch_mismatch if self.switch_checked else 1)
        return nums

    def numbers(self) -> dict:
        prompt_len = self.traffic["prompt_len"]
        nums = correctness.worst([
            correctness.job_numbers(self.reference, rec, prompt_len)
            for rec in self.job_records()])
        nums.update(self.state_numbers())
        return nums


def credited(jobs: Dict[str, JobRec], window) -> Dict[str, float]:
    t0, t1 = window
    return {j: rec.credit(t0, t1) for j, rec in jobs.items()}


def attempted_failed(jobs: Dict[str, JobRec], window) -> tuple:
    t0, t1 = window
    hit = [s for rec in jobs.values() for s in rec.steps
           if s[0] < t1 and (s[1] is None or s[1] > t0)]
    return len(hit), sum(1 for s in hit if s[1] is None)


def end_to_end(run: Run, out: dict) -> Dict[str, float]:
    t0, t1 = out["window"]
    w = t1 - t0
    credit = credited(run.jobs, out["window"])
    total = sum(credit.values())
    return {
        "chip_s_per_step": run.chips * w / total if total else float("inf"),
        "slowest_job_step_s": max(w / c if c else float("inf")
                                  for c in credit.values()),
        "setup_s": out["setup_s"],
    }


def run_record(run: Run, out: dict, peaks: dict) -> dict:
    """What the per-layer readers read (``bench/metrics/*.py``)."""
    t0, t1 = out["window"]
    credit = sum(credited(run.jobs, out["window"]).values())
    tr = run.traffic
    per_step = flops.step_flops(run.cfg["family"], run.reference.arch,
                                tr["prompts_per_step"] * tr["group_size"],
                                tr["seq_len"])
    rec = dict(window_s=t1 - t0, chips=run.chips, credited_steps=credit,
               flops=credit * sum(per_step.values()),
               peak_flops=peaks["bf16_flops"], hbm_bytes=peaks["hbm_bytes"],
               memory_peak_bytes=out["memory_peak_bytes"],
               switches_in_window=(out["switches_in_window"]
                                   if len(run.jobs) > 1 else None),
               trace=None)
    if run.tracing:
        rec["trace"] = Trace.from_file(find_xplane(run.trace_dir)).reduce()
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    return rec


def limits_for(cfg: dict, jobs: int) -> dict:
    """The configuration's limits that a cell of ``jobs`` jobs reads: one
    job makes no context switch to check."""
    return {k: v for k, v in cfg["limits"].items()
            if jobs > 1 or k != "switch_mismatch"}


def checks(numbers: dict, limits: dict) -> Dict[str, dict]:
    """Each limited number beside its limit; a number the run could not
    read fails its limit."""
    return {name: {"value": numbers.get(name, float("inf")), "limit": limit}
            for name, limit in limits.items()}


def is_correct(chk: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in chk.values())


def verdicts(state: dict, got: dict, limits: dict) -> Dict[str, bool]:
    """Whether a run would come out correct with the program's numbers of
    one job (``got["program"]``), and with each control or fault of
    :func:`correctness.calibration_numbers` in its place: a fault replaces
    the numbers it reads and keeps the program's others."""
    return {name: is_correct(checks({**state, **got["program"], **nums},
                                    limits))
            for name, nums in got.items() if name != "detail"}


def trace_dir_for(root: str, cell: str) -> str:
    return os.path.join(root, ".bench_traces", cell)


def steps_summary(jobs: Dict[str, JobRec]) -> Dict[str, int]:
    return {j: r.done() for j, r in jobs.items()}
