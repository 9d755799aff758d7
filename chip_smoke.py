#!/usr/bin/env python3
"""Smoke run of the served RLVR path on TPU chips.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: the per-group slice path

One chip. Two GRPO jobs of qwen2-0.5b at its published widths (random
weights from each job's seed) share one node group under
``PlexCluster.serve()``: each takes two steps through Router, HRRS
admission and its WPG, and the jobs context-switch on the chip. Then the
three Pallas kernels run natively at real widths against
``repro.kernels.ref``.

Four chips (``--chips 4``, this phase only). Two node groups hold disjoint
2-chip mesh slices with one full-width qwen2-0.5b job each, tensor-parallel
over the slice's ``model`` axis. After a step each, one job live-migrates
to the other slice (bit-identical params, every leaf on the destination)
and takes one more step there, whose loss is checked against the same
params and batch on a single chip. Last, a cross-slice ``sync_weights``
lands that job's weights on the shardings of a deployment on the other
slice.

Earlier stdout lines are JSON records worth reading (device, compile-
inclusive and later op times, switch seconds, peak device memory). The last
line is ``{"ok": true, "device": {...}}``. Any failed check raises: the
script then exits non-zero and prints no such line. So does a run without a
TPU: JAX_PLATFORMS defaults to ``tpu`` here, and any other platform is
refused.
"""
from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "tpu")

import argparse
import json
import math
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import api
from repro.core.cluster import PlexCluster
from repro.core.controller import JobConfig
from repro.core.state_manager import Tier
from repro.kernels import ops, ref
from repro.launch.compile_cache import use_compile_cache
from repro.rl import grpo

ARCH = "qwen2-0.5b"
# batch 16 = 4 prompts x group 4; 12 prompt + 16 new tokens packed into 64
JOB_SIZES = dict(batch_size=16, group_size=4, seq_len=64, max_new_tokens=16)
# bf16 kernel outputs vs f32-accumulated oracles (tests/test_kernels.py)
ATTN_TOL = dict(rtol=5e-2, atol=5e-2)
SSD_Y_TOL = dict(rtol=2e-2, atol=2e-2)
SSD_STATE_TOL = dict(rtol=1e-2, atol=1e-2)
# sharded vs one-chip loss from the same params and batch: the model runs
# in bf16 and the model-axis split reorders its reductions over 24 layers
LOSS_ATOL = 2e-2


def log(**record):
    print(json.dumps(record), flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def tpu_devices():
    """The TPU devices; any other platform is an error, never a fallback."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    return devs


def job_config(job_id: str, seed: int, steps: int,
               overrides: tuple = ()) -> JobConfig:
    return JobConfig(job_id=job_id, model_name=ARCH, steps=steps, seed=seed,
                     overrides=overrides, **JOB_SIZES)


def grade_and_keep(ctl) -> list:
    """Give every packed batch a learning signal, and record each batch the
    controller hands to UPDATE_ACTOR.

    Random weights never solve a problem, so every verifier reward is 0,
    and GRPO's group-relative advantages, hence its gradient, are exactly 0.
    The smoke grades the first half of each group correct instead: the
    advantages GRPO derives from that, and the rest of the batch as the
    controller packed it."""
    batches = []
    pack = ctl._pack
    group = ctl.cfg.group_size
    rewards = np.tile(np.arange(group) < group // 2,
                      ctl.cfg.batch_size // group).astype(np.float32)
    advantages = grpo.group_relative_advantages(jnp.asarray(rewards), group)

    def _pack(*args, **kwargs):
        batch = dict(pack(*args, **kwargs), advantages=advantages)
        batches.append(batch)
        return batch

    ctl._pack = _pack
    return batches


def to_host(tree):
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a)), tree)


def leaf_device_ids(tree) -> set:
    return {d.id for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def op_seconds(wpg) -> list:
    return [[op, round(s, 4)] for op, s in wpg.exec_log]


def check_job(cluster: PlexCluster, ctl, batches: list, steps: int):
    """Finite losses, in-vocabulary tokens, billed time, and params that
    moved away from their seeded init."""
    job = ctl.cfg.job_id
    losses = [m["loss"] for m in ctl.metrics_log]
    check(len(losses) == steps, f"{job}: {len(losses)} of {steps} steps")
    check(all(math.isfinite(x) for x in losses), f"{job}: losses {losses}")
    wpg = cluster.router.wpgs[ctl.dep.spec.deployment_id]
    vocab = wpg.cfg.vocab_size
    toks = np.concatenate([np.asarray(b["tokens"]) for b in batches])
    check(0 <= toks.min() and toks.max() < vocab,
          f"{job}: token ids in [{toks.min()}, {toks.max()}], vocab {vocab}")
    bill = cluster.billing[job]
    check(bill.busy_seconds > 0, f"{job}: billed {bill.busy_seconds}s")
    # Not every leaf moves: AdamW's first steps change a weight by about
    # the warm-up learning rate (3e-6), below half a bf16 ulp of most
    # weights, and there is no f32 master copy. Zero-initialized leaves
    # (the QKV biases) record any update at all.
    init = wpg.model.init_params(jax.random.PRNGKey(ctl.cfg.seed))
    pairs = zip(jax.tree.leaves(wpg.params()), jax.tree.leaves(init))
    moved = [bool(jnp.any(jnp.asarray(a) != b)) for a, b in pairs]
    check(any(moved), f"{job}: every param leaf equals its init")
    log(phase="served", job=job, losses=losses,
        grad_norms=[m["grad_norm"] for m in ctl.metrics_log],
        leaves_moved=f"{sum(moved)}/{len(moved)}", busy_s=bill.busy_seconds,
        switch_s=bill.switch_seconds, op_seconds=op_seconds(wpg))


def served_phase(overrides: tuple = (), steps: int = 2):
    """Two GRPO jobs on one node group under the persistent serve plane."""
    cluster = PlexCluster(n_groups=1)
    jobs = []
    for seed, job in enumerate(("jobA", "jobB")):
        ctl = cluster.add_job(job_config(job, seed, steps, overrides))
        jobs.append((ctl, grade_and_keep(ctl)))
    t0 = time.monotonic()
    with cluster.serve():
        pass                  # the jobs' client threads run to completion
    wall = time.monotonic() - t0
    switches = cluster.router.switch_log
    check(len(switches) > 0, "no context switch between the jobs")
    for ctl, batches in jobs:
        check_job(cluster, ctl, batches, steps)
    log(phase="served", wall_s=wall, switches=len(switches),
        switch_s=[round(e["t_offload"] + e["t_load"], 4) for e in switches])


def run_native(fn, *args):
    """Compile ``fn`` for the chip, require a Mosaic kernel in it, run it.
    Returns (outputs, seconds of the first run after compilation)."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(), "kernel not compiled in")
    t0 = time.monotonic()
    out = jax.block_until_ready(compiled(*args))
    return out, time.monotonic() - t0


def max_err(name: str, got, want, tol: dict) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, err_msg=name, **tol)
    return float(np.max(np.abs(got - want)))


def kernel_phase():
    """Flash and decode attention at qwen2-0.5b's heads, SSD at
    mamba2-2.7b's, each native on the chip against its oracle (the oracles
    at full f32 matmul precision)."""
    qwen = get_config(ARCH)
    h, kh, d = qwen.num_heads, qwen.num_kv_heads, qwen.resolved_head_dim
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 12))
    bf16 = lambda shape: jax.random.normal(next(ks), shape, jnp.bfloat16)

    b, s = 2, 1024
    q, k, v = bf16((b, s, h, d)), bf16((b, s, kh, d)), bf16((b, s, kh, d))
    out, secs = run_native(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True), q, k, v)
    with jax.default_matmul_precision("highest"):
        want = ref.ref_attention(q, jnp.repeat(k, h // kh, 2),
                                 jnp.repeat(v, h // kh, 2), causal=True)
    log(phase="kernels", kernel="flash", batch=b, seq=s, heads=h,
        kv_heads=kh, head_dim=d, run_s=secs,
        max_abs_err=max_err("flash", out, want, ATTN_TOL))

    b, t, pos = 8, 2048, 1500
    q, kc, vc = bf16((b, h, d)), bf16((b, t, kh, d)), bf16((b, t, kh, d))
    out, secs = run_native(
        lambda q, k, v: ops.decode_attention(q, k, v, pos), q, kc, vc)
    with jax.default_matmul_precision("highest"):
        want = ref.ref_decode_attention(q, kc, vc, pos)
    log(phase="kernels", kernel="decode", batch=b, heads=h, kv_heads=kh,
        cache=t, pos=pos, run_s=secs,
        max_abs_err=max_err("decode", out, want, ATTN_TOL))

    mamba = get_config("mamba2-2.7b")
    h, p, n, chunk = (mamba.ssm_nheads, mamba.ssm_head_dim, mamba.ssm_state,
                      mamba.ssm_chunk)
    x = bf16((1, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(next(ks), (1, s, h)))
    A = -jnp.exp(jax.random.normal(next(ks), (h,)))
    B = jax.random.normal(next(ks), (1, s, 1, n))
    C = jax.random.normal(next(ks), (1, s, 1, n))
    (y, state), secs = run_native(
        lambda x, dt, A, B, C: ops.ssd(x, dt, A, B, C, chunk=chunk),
        x, dt, A, B, C)
    with jax.default_matmul_precision("highest"):
        y_want, state_want = ref.ref_ssd(x.astype(jnp.float32), dt, A, B, C,
                                         chunk=chunk)
    log(phase="kernels", kernel="ssd", seq=s, heads=h, head_dim=p,
        d_state=n, chunk=chunk, run_s=secs,
        max_abs_err_y=max_err("ssd y", y, y_want, SSD_Y_TOL),
        max_abs_err_state=max_err("ssd state", state, state_want,
                                  SSD_STATE_TOL))


def slice_phase(overrides: tuple = ()):
    """Per-group 2-chip slices: TP placement, live migration with a step on
    the destination, the sharded loss against one chip, and a cross-slice
    weight sync. Each stage logs a record when its checks pass, so a run
    that fails names the last stage that held."""
    cluster = PlexCluster(n_groups=2, devices_per_group=2)
    router = cluster.router
    ctls, batches = {}, {}
    for g, job in enumerate(("jobA", "jobB")):
        ctls[job] = cluster.add_job(job_config(job, g, 1, overrides),
                                    group_id=g)
        batches[job] = grade_and_keep(ctls[job])
    with cluster.serve():
        pass
    slices = {g: set(router.device_plane.slice_for_group(g).device_ids())
              for g in (0, 1)}
    check(len(slices[0]) == 2 == len(slices[1])
          and slices[0].isdisjoint(slices[1]), f"slices {slices}")
    wpgs = {j: router.wpgs[f"{j}-train"] for j in ctls}
    for g, job in enumerate(ctls):
        check_job(cluster, ctls[job], batches[job], 1)
        params = wpgs[job].params()
        check(leaf_device_ids(params) == slices[g],
              f"{job} params on {leaf_device_ids(params)}, slice {slices[g]}")
        split = [leaf for leaf in jax.tree.leaves(params)
                 if "model" in jax.tree.leaves(tuple(leaf.sharding.spec))]
        check(len(split) > 0, f"{job}: no leaf split over 'model'")
    log(phase="slices", stage="placed",
        slices={g: sorted(s) for g, s in slices.items()})

    # live migration of jobA onto group 1's slice
    src = wpgs["jobA"]
    before = to_host(src.params())
    moved = cluster.reassign_job("jobA", 1)
    mig = router.migrate_log[-1]
    check(moved > 0 and mig["cross_mesh"], f"migration {mig}")
    for a, b in zip(jax.tree.leaves(to_host(src.params())),
                    jax.tree.leaves(before)):
        check(np.array_equal(a, b), "migrated params differ")
    sm = src.sm
    state_ids = {d.id for k in sm.keys_for(src.job_prefix)
                 if sm.entries[k].tier == Tier.DEVICE
                 for d in sm.entries[k].ref.devices()}
    check(state_ids == slices[1], f"jobA state on {state_ids} after "
                                  f"migrating to {slices[1]}")
    log(phase="slices", stage="migrated", bytes=mig["bytes"],
        seconds=mig["seconds"])

    # one more step on the destination slice, from the migrated state
    ctls["jobA"].cfg.steps = 2
    with cluster.serve():
        pass
    sharded = ctls["jobA"].metrics_log[-1]
    check(math.isfinite(sharded["loss"]), f"post-migration loss {sharded}")
    check(leaf_device_ids(src.params()) == slices[1],
          "jobA params left the destination slice")
    log(phase="slices", stage="stepped", sharded_loss=sharded["loss"])

    # that step's loss from the same params and batch on one chip
    one = jax.devices()[0]
    loss, _ = jax.jit(lambda p, b: grpo.grpo_loss(p, src.model, b,
                                                  src.grpo_cfg))(
        jax.device_put(before, one), jax.device_put(batches["jobA"][-1], one))
    check(abs(sharded["loss"] - float(loss)) <= LOSS_ATOL,
          f"sharded loss {sharded['loss']} vs one chip {float(loss)}")
    log(phase="slices", stage="one_chip", one_chip_loss=float(loss),
        abs_diff=abs(sharded["loss"] - float(loss)))

    # cross-slice weight sync: jobA's train params (group 1) into a rollout
    # deployment of jobA on group 0
    trained = to_host(src.params())
    roll = router.deploy(api.DeploymentSpec(
        deployment_id="jobA-rollout", job_id="jobA", model_name=ARCH,
        role="rollout", overrides=overrides), group_id=0)
    sync_f = ctls["jobA"].dep.sync_weights(roll)
    router.run_until_idle(timeout=600)
    sync_f.result()
    synced = roll.wpg.params()
    want = jax.tree.leaves(roll.wpg.param_shardings())
    for leaf, sh in zip(jax.tree.leaves(synced), want):
        check(leaf.sharding.is_equivalent_to(sh, leaf.ndim),
              f"synced leaf on {leaf.sharding}, target {sh}")
    check(leaf_device_ids(synced) == slices[0],
          f"synced params on {leaf_device_ids(synced)}, slice {slices[0]}")
    for a, b in zip(jax.tree.leaves(to_host(synced)),
                    jax.tree.leaves(trained)):
        check(np.array_equal(a, b), "synced weights differ from the source")
    router.teardown("jobA-rollout")
    log(phase="slices", stage="synced",
        op_seconds={j: op_seconds(w) for j, w in wpgs.items()})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the per-group slice phase only")
    args = ap.parse_args(argv)
    devs = tpu_devices()
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} but {len(devs)} devices")
    cache = use_compile_cache(args.chips)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(phase="setup", device=device, compile_cache=cache)
    t0 = time.monotonic()
    if args.chips == 4:
        slice_phase()
    else:
        served_phase()
        kernel_phase()
    log(phase="done", wall_s=time.monotonic() - t0,
        peak_bytes_in_use=[(d.memory_stats() or {}).get("peak_bytes_in_use")
                           for d in devs])
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
