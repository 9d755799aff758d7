"""The served path's spans and counters (core/telemetry.py).

Every number here is exact: times come from a VirtualClock or a counting
clock, or from JAX's own compile events, never from wall-clock ratios.
"""
import glob
import itertools
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.controller import JobConfig, RLControllerGRPO
from repro.core.router import Router
from repro.core.scheduler.executor import VirtualClock
from repro.core.telemetry import Counters

WAIT_S = 30.0          # bound on waiting for the watcher thread
TINY = (("num_layers", 2), ("d_model", 32), ("num_heads", 4),
        ("num_kv_heads", 2), ("head_dim", 8), ("d_ff", 64),
        ("vocab_size", 64), ("tie_embeddings", True))
TINY_SSM = (("num_layers", 2), ("d_model", 32), ("vocab_size", 64),
            ("ssm_state", 8), ("ssm_head_dim", 8), ("ssm_chunk", 4))


class ClockStub:
    """Execution backend on a VirtualClock: each op advances the clock by
    its kind's duration and logs (req_id, start, end)."""

    def __init__(self, spec, sm, clock, durations, log):
        self.spec, self.sm = spec, sm
        self.exec_log = []
        self._clock, self._durations, self._log = clock, durations, log

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
        start = self._clock.now()
        end = self._clock.advance(self._durations.get(qop.op, 0.0))
        self._log.append((qop.req_id, start, end))
        return {"req_id": qop.req_id}


def clock_router(durations, n_jobs=2):
    clock, log = VirtualClock(), []
    router = Router(now=clock, wpg_factory=lambda spec, sm: ClockStub(
        spec, sm, clock, durations, log))
    specs = []
    for j in range(n_jobs):
        spec = api.DeploymentSpec(deployment_id=f"dep{j}", job_id=f"job{j}",
                                  model_name="stub", role="train")
        router.create_deployment(spec, group_id=0)
        specs.append(spec)
    return router, specs, clock, log


def host_events(trace_dir, prefix="plexrl."):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events
                        if e.name.startswith(prefix)]
    return out


def test_op_span_carries_job_req_and_cause_in_the_trace(tmp_path):
    router, (a, _), _, _ = clock_router({})
    gen = router.submit_queued_operation(
        api.make_op(a, api.Op.GENERATE, [1, 2]))
    upd = router.submit_queued_operation(
        api.make_op(a, api.Op.UPDATE_ACTOR, gen))
    jax.profiler.start_trace(str(tmp_path))
    try:
        router.drain()
    finally:
        jax.profiler.stop_trace()
    upd.result()
    events = host_events(str(tmp_path))
    ops = {name: stats for name, stats in events
           if name.startswith("plexrl.op.")}
    assert ops["plexrl.op.GENERATE"]["job"] == "job0"
    assert ops["plexrl.op.GENERATE"]["req"] == gen.sources[0]
    # the update waited on the generate: its cause names that request
    assert str(ops["plexrl.op.UPDATE_ACTOR"]["cause"]) == str(gen.sources[0])
    callbacks = [s["req"] for n, s in events if n == "plexrl.callbacks"]
    assert sorted(callbacks) == sorted([gen.sources[0], upd.sources[0]])


def test_queue_wait_runs_from_admissible_to_started():
    """Two jobs on one group: a wait behind the other job's op counts, a
    wait on a prerequisite does not."""
    dur = {api.Op.GENERATE: 3.0, api.Op.UPDATE_ACTOR: 1.0}
    router, (a, b), clock, log = clock_router(dur)
    gen_a = router.submit_queued_operation(
        api.make_op(a, api.Op.GENERATE, 0))
    gen_b = router.submit_queued_operation(
        api.make_op(b, api.Op.GENERATE, 0))
    upd_a = router.submit_queued_operation(
        api.make_op(a, api.Op.UPDATE_ACTOR, 0, prerequisites=(gen_a,)))
    a1, b1, a2 = (f.sources[0] for f in (gen_a, gen_b, upd_a))
    assert router.drain() == 3
    start = {req: s for req, s, _ in log}
    end = {req: e for req, _, e in log}
    # everything arrived at 0; the update became admissible when a1 ended
    waits = {a1: start[a1], b1: start[b1], a2: start[a2] - end[a1]}
    snap = router.telemetry.snapshot()
    assert snap["dispatch.admitted"] == 3
    assert snap["dispatch.queue_wait_s"] == pytest.approx(
        sum(waits.values()), abs=1e-12)
    assert snap["dispatch.queue_wait_s.UPDATE_ACTOR"] == pytest.approx(
        waits[a2], abs=1e-12)
    assert snap["dispatch.queue_wait_s.GENERATE"] == pytest.approx(
        waits[a1] + waits[b1], abs=1e-12)
    # timed from submission instead, the update's wait on its own
    # prerequisite would count too
    assert sum(start.values()) - snap["dispatch.queue_wait_s"] == \
        pytest.approx(end[a1], abs=1e-12)


def test_callback_time_is_the_time_client_callbacks_hold_dispatch():
    router, (a, _), clock, _ = clock_router({api.Op.GENERATE: 1.0})
    f1 = router.submit_queued_operation(api.make_op(a, api.Op.GENERATE, 0))
    f2 = router.submit_queued_operation(api.make_op(a, api.Op.GENERATE, 1))
    f1.then(lambda r: clock.advance(2.5))
    f2.then(lambda r: clock.advance(0.5))
    router.drain()
    snap = router.telemetry.snapshot()
    assert snap["dispatch.callback_s"] == pytest.approx(3.0, abs=1e-12)
    assert snap["op.GENERATE.count"] == 2
    assert snap["op.GENERATE.host_s"] == pytest.approx(2.0, abs=1e-12)


def wait_for(counters, name):
    deadline = time.monotonic() + WAIT_S
    while name not in counters.snapshot():
        assert time.monotonic() < deadline, f"{name} never counted"
        time.sleep(0.01)
    return counters.snapshot()[name]


def test_switch_counters_move_the_jobs_bytes(tmp_path):
    """Real WPGs on one group: the second job's op offloads the first job,
    the first job's op offloads the second and loads the first back."""
    router = Router()
    deps = []
    for j in range(2):
        spec = api.DeploymentSpec(deployment_id=f"d{j}", job_id=f"j{j}",
                                  model_name="qwen2-0.5b", role="train",
                                  overrides=TINY)
        deps.append(router.deploy(spec, group_id=0))
    for j, dep in enumerate(deps):
        dep.init(seed=j)
    router.drain()
    sm = router.state_managers[0]
    nbytes = [sm.job_bytes(dep.wpg.job_prefix) for dep in deps]
    assert all(nbytes)
    leaves = [len(sm.keys_for(dep.wpg.job_prefix)) for dep in deps]
    deps[1].save_checkpoint(str(tmp_path / "b"))
    router.drain()
    deps[0].save_checkpoint(str(tmp_path / "a"))
    router.drain()
    snap = router.telemetry.snapshot()
    assert snap["switch.count"] == 2
    assert snap["switch.offload_bytes"] == nbytes[0] + nbytes[1]
    # each offload moved all of its job's leaves in one batch
    assert snap["switch.offload_leaves"] == leaves[0] + leaves[1]
    assert snap["switch.load_bytes"] == nbytes[0]
    assert snap["op.SAVE_CHECKPOINT.count"] == 2
    assert wait_for(router.telemetry, "switch.load_s") >= 0.0
    # a switch the benchmark counts is one that offloaded a job
    assert sum(1 for e in router.switch_log if e["t_offload"] > 0) == 2


@pytest.mark.parametrize("model,overrides", [("qwen2-0.5b", TINY),
                                              ("mamba2-2.7b", TINY_SSM)])
def test_generate_counts_its_decode_state_and_steps(model, overrides):
    """One GENERATE on the served path adds the bytes of the decode cache
    it carries through its loop (a KV cache over prompt and completion;
    a conv window and SSM state) and the positions it decodes."""
    router = Router()
    spec = api.DeploymentSpec(deployment_id="d0", job_id="j0",
                              model_name=model, role="train",
                              overrides=overrides)
    dep = router.deploy(spec, group_id=0)
    dep.init(seed=0)
    rows, prompt, new = 3, 5, 6
    dep.generate(np.full((rows, prompt), 7, np.int32), max_new_tokens=new)
    router.drain()
    snap = router.telemetry.snapshot()
    cache = dep.wpg.model.abstract_cache(rows, prompt + new)
    assert snap["generate.state_bytes"] == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert snap["generate.decode_steps"] == new


class GatedLeaf:
    """A leaf that becomes ready when ``gate`` is set. On the CPU backend a
    computation holding a host callback (``jax.pure_callback``) runs inline
    in the dispatching thread, so no real array can be held back without
    holding its caller; the watcher waits on any leaf that can block, as
    ``jax.block_until_ready`` does."""

    def __init__(self, gate):
        self.gate = gate

    def block_until_ready(self):
        assert self.gate.wait(WAIT_S)
        return self

    def is_deleted(self):
        return False


def test_watcher_never_holds_the_caller_and_stamps_once_ready():
    clock = VirtualClock()
    counters = Counters(clock)
    gate, stamped = threading.Event(), []
    counters.when_ready({"out": GatedLeaf(gate)}, stamped.append)
    assert not gate.is_set() and stamped == []     # the caller went on
    clock.advance(5.0)
    gate.set()
    deadline = time.monotonic() + WAIT_S
    while not stamped:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert stamped == [5.0]
    # nothing to wait on: called at once, on the caller's thread
    counters.when_ready({"n": 3, "host": np.ones(2)}, stamped.append)
    assert stamped == [5.0, 5.0]


def test_watcher_counts_a_donated_array_as_ready():
    counters = Counters(VirtualClock(2.0))
    x = jnp.ones(4) + 1
    x.delete()
    counters.when_ready([x], lambda t: counters.add("ready_at", t),
                        donated="switch.load_donated")
    assert wait_for(counters, "ready_at") == 2.0
    assert counters.snapshot()["switch.load_donated"] == 1


def test_compile_counters_rise_for_a_fresh_jit_only():
    counters = Counters()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + x.sum())
    x = jnp.arange(7.0)
    before = counters.snapshot()
    f(x).block_until_ready()
    fresh = counters.snapshot()
    f(x).block_until_ready()
    cached = counters.snapshot()
    names = ("jax.trace_s", "jax.lower_s", "jax.compile_s", "jax.compiles")
    for name in names:
        assert fresh.get(name, 0.0) > before.get(name, 0.0), name
        assert cached.get(name, 0.0) == fresh[name], name
    assert fresh["jax.compiles"] == before.get("jax.compiles", 0.0) + 1


def test_pack_counts_its_reward_inside_it(tmp_path):
    ticks = itertools.count()
    router = Router(now=lambda: float(next(ticks)),
                    wpg_factory=lambda spec, sm: ClockStub(
                        spec, sm, VirtualClock(), {}, []))
    cfg = JobConfig(job_id="j0", model_name="stub")
    ctl = RLControllerGRPO(cfg, router)
    prompts, problems = next(ctl.batches)
    n = len(prompts)
    gen = {"tokens": np.zeros((n, cfg.max_new_tokens), np.int32),
           "logprobs": np.zeros((n, cfg.max_new_tokens), np.float32)}
    jax.profiler.start_trace(str(tmp_path))
    try:
        batch = ctl._pack(prompts, [p.answer for p in problems], gen)
    finally:
        jax.profiler.stop_trace()
    assert batch["tokens"].shape[0] == n
    snap = router.telemetry.snapshot()
    assert 0 < snap["client.reward_s"] < snap["client.pack_s"]
    names = [name for name, _ in host_events(str(tmp_path))]
    assert sorted(names) == ["plexrl.pack", "plexrl.pack.fetch",
                             "plexrl.reward"]
