"""Process plane (launch/proc_plane.py): per-group worker processes.

Covers the IPC dispatch protocol end to end — spawn + ready handshake,
execute round trips with the parent-side ExecLog mirror, remote errors vs
child death (poisoned dependents either way), the liveness heartbeat,
serve-mode attach, crash → capacity-adjuster respawn with billing
conservation (the PR's robustness satellite), the StateManager
export/import halves (inline + disk-spill), cross-process migration and
weight sync with REAL jax WPGs, and the compute-overlap acceptance (two
children's compute-bound ops run at the same time; needs ≥ 2 cores, so it
skips on single-core boxes where overlap is physically impossible).

Stub children use ``repro.launch.stub_wpg`` (factories cross the spawn
boundary by NAME) and never import jax, so this module stays fast; the one
real-model test uses the same tiny overrides as test_system.py.
"""
import glob
import os
import signal

import numpy as np
import pytest

from repro.core import api
from repro.core.cluster import BillingRecord, PlexCluster
from repro.core.router import Router
from repro.core.state_manager import StateManager, Tier
from repro.launch import shm_transport as shmt
from repro.launch.proc_plane import GroupProcessError

STUB = "repro.launch.stub_wpg:make_busy_wpg"
CRASH_STORE = "repro.launch.stub_wpg:make_crash_store_wpg"

needs_shm = pytest.mark.skipif(
    not shmt.shm_available(), reason="no usable shared memory on this host")


def my_shm_segments():
    """Live /dev/shm segments created by THIS parent process's plane."""
    prefix = f"pxl{os.getpid()}g"
    try:
        return sorted(n for n in os.listdir(shmt.SHM_DIR)
                      if n.startswith(prefix))
    except FileNotFoundError:
        return []


def make_proc_router(n_groups=2, factory=STUB):
    r = Router(process_plane=True, proc_wpg_factory=factory)
    specs = []
    for g in range(n_groups):
        spec = api.DeploymentSpec(deployment_id=f"dep{g}", job_id=f"job{g}",
                                  model_name="stub", role="train")
        r.create_deployment(spec, group_id=g)
        specs.append(spec)
    return r, specs


# ------------------------------------------------------------ dispatch
def test_execute_roundtrip_and_log_mirror():
    r, specs = make_proc_router(n_groups=2)
    try:
        futs = [r.submit_queued_operation(
            api.make_op(s, api.Op.FORWARD, 0)) for s in specs]
        assert r.run_until_idle(timeout=120) == 2
        pids = {f.result()["pid"] for f in futs}
        # each group's ops really ran in its own OS process (≠ parent)
        assert len(pids) == 2 and os.getpid() not in pids
        for s in specs:
            log = list(r.wpgs[s.deployment_id].exec_log)
            assert len(log) == 1 and log[0][0] == "forward"
        assert not r.pending
    finally:
        r.close_processes()


def test_remote_error_poisons_dependents_child_survives():
    r, specs = make_proc_router(n_groups=1)
    try:
        bad = api.make_op(specs[0], api.Op.FORWARD, 0, fail=True)
        dep = api.make_op(specs[0], api.Op.FORWARD, 1,
                          prerequisites=(bad.req_id,))
        f_bad = r.submit_queued_operation(bad)
        f_dep = r.submit_queued_operation(dep)
        r.run_until_idle(timeout=120)
        with pytest.raises(RuntimeError, match="asked to fail"):
            f_bad.result()
        with pytest.raises(RuntimeError, match="prerequisite"):
            f_dep.result()
        # an op ERROR is not a child DEATH: the process keeps serving
        assert r.process_health() == {0: True}
        f_ok = r.submit_queued_operation(
            api.make_op(specs[0], api.Op.FORWARD, 2))
        r.run_until_idle(timeout=120)
        assert f_ok.result()["op"] == "forward"
    finally:
        r.close_processes()


def test_heartbeat_and_health():
    r, _ = make_proc_router(n_groups=1)
    try:
        rtt = r.group_procs[0].ping(timeout=30.0)
        assert rtt is not None and 0.0 <= rtt < 30.0
        assert r.process_health() == {0: True}
        telem = r.group_telemetry()
        assert telem[0]["process_alive"] is True
    finally:
        r.close_processes()
    assert r.process_health() == {}


def test_serve_mode_attach():
    r, specs = make_proc_router(n_groups=1)
    try:
        with r:
            f = r.submit_queued_operation(
                api.make_op(specs[0], api.Op.FORWARD, 0))
            assert f.wait(timeout=120)
            assert f.result()["op"] == "forward"
            # dynamic attach on a NEW group spawns its worker process
            spec2 = api.DeploymentSpec(deployment_id="dep9", job_id="job9",
                                       model_name="stub", role="train")
            r.create_deployment(spec2, group_id=5)
            f2 = r.submit_queued_operation(
                api.make_op(spec2, api.Op.FORWARD, 0))
            assert f2.wait(timeout=120)
        assert set(r.process_health()) == {0, 5}
    finally:
        r.close_processes()


# --------------------------------------------- robustness: crash mid-op
def test_worker_process_crash_respawn_and_billing_conserved():
    """The PR's robustness satellite: a worker process dying mid-op fails
    the RUNNING op, poisons its dependents, is respawned by the capacity
    adjuster on the next director poll, and billing for ops completed
    BEFORE the crash is conserved (the ExecLog mirror lives parent-side)."""
    c = PlexCluster(n_groups=1, process_plane=True, proc_wpg_factory=STUB)
    r = c.router
    spec = api.DeploymentSpec(deployment_id="dep0", job_id="job0",
                              model_name="stub", role="train")
    r.create_deployment(spec, group_id=0)
    c.billing["job0"] = BillingRecord(job_id="job0")
    try:
        ok = r.submit_queued_operation(
            api.make_op(spec, api.Op.FORWARD, 0, sleep_s=0.01))
        bad = api.make_op(spec, api.Op.FORWARD, 1, crash=True)
        f_bad = r.submit_queued_operation(bad)
        f_dep = r.submit_queued_operation(
            api.make_op(spec, api.Op.FORWARD, 2,
                        prerequisites=(bad.req_id,)))
        r.run_until_idle(timeout=120)
        assert ok.result()["seconds"] >= 0.01
        with pytest.raises(RuntimeError, match="worker process died"):
            f_bad.result()
        with pytest.raises(RuntimeError, match="prerequisite"):
            f_dep.result()
        assert r.process_health() == {0: False}
        # billing for the COMPLETED op survives the crash (mirror log)
        c._bill_from_logs()
        assert c.billing["job0"].busy_seconds >= 0.01
        billed_before = c.billing["job0"].busy_seconds
        # the capacity adjuster is the supervisor: poll respawns the group
        c.director.poll()
        assert [e for e in c.director.events
                if e["event"] == "respawn_group" and e["group"] == 0]
        assert r.process_health() == {0: True}
        # the replayed deployment serves again, and billing keeps flowing
        f2 = r.submit_queued_operation(
            api.make_op(spec, api.Op.FORWARD, 3, sleep_s=0.01))
        r.run_until_idle(timeout=120)
        assert f2.result()["op"] == "forward"
        c._bill_from_logs()
        assert c.billing["job0"].busy_seconds > billed_before
    finally:
        r.close_processes()


# -------------------------------------------------- migration transport
def test_export_import_roundtrip_with_disk_spill(tmp_path):
    """The migrate-export/import halves in isolation (no processes): host
    staging, PartitionSpec/bf16 wire encoding, and the disk-tier fallback
    for entries above max_inline_bytes (spill files consumed on import)."""
    src = StateManager(node_id="src", disk_dir=str(tmp_path / "src"))
    dst = StateManager(node_id="dst", disk_dir=str(tmp_path / "dst"))
    big = np.arange(4096, dtype=np.float32).reshape(64, 64)
    small = np.ones(8, np.float32)
    src.register("jobA:dep0", {"w": big, "b": small}, Tier.HOST)
    payload = src.export_state("jobA:dep0", max_inline_bytes=1024)
    spilled = [e for e in payload["entries"] if e["path"] is not None]
    inline = [e for e in payload["entries"] if e["data"] is not None]
    assert len(spilled) == 1 and len(inline) == 1   # big spills, small rides
    assert os.path.exists(spilled[0]["path"])
    moved = dst.import_state(payload)
    assert moved == payload["bytes"] == big.nbytes + small.nbytes
    got = dst.gather("jobA:dep0", {"w": big, "b": small})
    np.testing.assert_array_equal(np.asarray(got["w"]), big)
    np.testing.assert_array_equal(np.asarray(got["b"]), small)
    assert not os.path.exists(spilled[0]["path"])   # spill consumed
    assert dst.last_migrate["keys"] == 2
    # transactional import: a corrupt payload rolls back staged entries
    bad = {"entries": [
        {"key": "jobB:dep0/params/x", "nbytes": 8, "version": 0,
         "tier": int(Tier.HOST), "is_bf16": False, "spec": None,
         "path": None, "data": np.ones(2, np.float32)},
        {"key": "jobB:dep0/params/y", "nbytes": 8, "version": 0,
         "tier": int(Tier.HOST), "is_bf16": False, "spec": None,
         "path": str(tmp_path / "missing.npy"), "data": None}]}
    with pytest.raises(Exception):
        dst.import_state(bad)
    assert dst.keys_for("jobB:dep0") == []


def test_real_wpg_cross_process_sync_and_migration():
    """Real jax WPGs in child processes: INIT in two groups, cross-process
    weight sync (host-staged params over the pipe, device_put on the
    target's shardings), GENERATE in the child, then a live cross-process
    migration (export → import → rehome) after which the plane still
    serves."""
    tiny = (("num_layers", 2), ("d_model", 32), ("num_heads", 4),
            ("num_kv_heads", 2), ("head_dim", 8), ("d_ff", 64),
            ("vocab_size", 64), ("tie_embeddings", True))
    r = Router(process_plane=True)      # default factory: real WPG
    train = api.DeploymentSpec(deployment_id="train0", job_id="jobA",
                               model_name="qwen2-0.5b", role="train",
                               overrides=tiny)
    roll = api.DeploymentSpec(deployment_id="roll0", job_id="jobA",
                              model_name="qwen2-0.5b", role="rollout",
                              overrides=tiny)
    try:
        r.create_deployment(train, group_id=0)
        r.create_deployment(roll, group_id=1)
        d_train, d_roll = api.Deployment(train, r), api.Deployment(roll, r)
        f_a = r.submit_queued_operation(api.make_op(train, api.Op.INIT, 0))
        f_b = r.submit_queued_operation(api.make_op(roll, api.Op.INIT, 0))
        r.run_until_idle(timeout=280)
        assert f_a.result()["params"] == f_b.result()["params"] > 0
        f_sync = d_train.sync_weights(d_roll)
        r.run_until_idle(timeout=280)
        assert f_sync.result()["synced_bytes"] > 0
        f_gen = r.submit_queued_operation(
            api.make_op(roll, api.Op.GENERATE, [[1, 2, 3]],
                        max_new_tokens=4))
        r.run_until_idle(timeout=280)
        toks = f_gen.result()["tokens"]
        assert isinstance(toks, np.ndarray) and toks.shape == (1, 4)
        # live migration of the whole job onto a fresh third group/process
        moved = r.reassign_job("jobA", 2, timeout=280)
        assert moved > 0
        assert r.state_managers[2].job_bytes("jobA:train0") > 0
        assert r.group_of["train0"] == r.group_of["roll0"] == 2
        f_gen2 = r.submit_queued_operation(
            api.make_op(roll, api.Op.GENERATE, [[1, 2, 3]],
                        max_new_tokens=4))
        r.run_until_idle(timeout=280)
        assert f_gen2.result()["tokens"].shape == (1, 4)
    finally:
        r.close_processes()


# ---------------------------------------------------- overlap acceptance
@pytest.mark.skipif(len(os.sched_getaffinity(0)) < 2,
                    reason="compute overlap needs >= 2 CPU cores")
def test_process_plane_overlaps_compute_bound_groups():
    """The process plane's reason to exist: two groups' compute-bound
    (GIL-holding) ops execute at the same time in their own processes.
    Asserted on the children's own execute intervals, not on wall-clock
    ratios, which a loaded host can stretch either way."""
    r, specs = make_proc_router(n_groups=2)
    try:
        for s in specs:     # warm: spawn + handshake before the probe
            r.submit_queued_operation(api.make_op(s, api.Op.FORWARD, 0))
        r.run_until_idle(timeout=120)
        futs = {s.deployment_id: [r.submit_queued_operation(
            api.make_op(s, api.Op.FORWARD, i, busy_s=0.2))
            for i in range(3)] for s in specs}
        assert r.run_until_idle(timeout=120) == 6
        spans = {dep: [(f.result()["t0"], f.result()["t1"]) for f in fs]
                 for dep, fs in futs.items()}
        pids = {dep: {f.result()["pid"] for f in fs}
                for dep, fs in futs.items()}
        assert pids["dep0"].isdisjoint(pids["dep1"])
        # some op of group 0 ran while some op of group 1 was running
        assert any(a0 < b1 and b0 < a1
                   for a0, a1 in spans["dep0"] for b0, b1 in spans["dep1"]), \
            spans
    finally:
        r.close_processes()


# ------------------------------------------------- shared-memory transport
@needs_shm
def test_shm_execute_reply_roundtrip_and_no_residue():
    """A large execute result rides shm descriptors, not the pipe: the
    decoded value is bit-identical, the parent really saw child-pool
    segments, and a closed plane leaves /dev/shm spotless."""
    r = Router(process_plane=True, proc_wpg_factory=STUB)
    spec = api.DeploymentSpec(deployment_id="dep0", job_id="job0",
                              model_name="stub", role="train")
    r.create_deployment(spec, group_id=0)
    try:
        futs = [r.submit_queued_operation(
            api.make_op(spec, api.Op.FORWARD, i, payload_mb=4))
            for i in range(3)]
        r.run_until_idle(timeout=120)
        want = np.arange((4 << 20) // 8, dtype=np.float64)
        for f in futs:
            got = f.result()["data"]
            assert got.base is None           # an owning copy, not a view
            np.testing.assert_array_equal(got, want)
        # the replies actually used the descriptor path…
        assert r.group_procs[0]._seen_child_segs
        # …and pooling kept it to one segment across the repeats
        assert len(r.group_procs[0]._seen_child_segs) == 1
    finally:
        r.close_processes()
    assert my_shm_segments() == []


@needs_shm
def test_shm_cross_child_sync_checksum():
    """Cross-child sync_weights as a descriptor relay: source child writes
    its params once into ITS pool, the target child consumes the views —
    the parent never touches the bytes — and the landed params checksum
    exactly."""
    mb = 4
    r = Router(process_plane=True, proc_wpg_factory=STUB)
    src = api.DeploymentSpec(deployment_id="src0", job_id="jobS",
                             model_name="stub", role="train",
                             overrides=(("sync_mb", mb),))
    dst = api.DeploymentSpec(deployment_id="dst0", job_id="jobS",
                             model_name="stub", role="rollout")
    try:
        r.create_deployment(src, group_id=0)
        r.create_deployment(dst, group_id=1)
        d_src, d_dst = api.Deployment(src, r), api.Deployment(dst, r)
        f_sync = d_src.sync_weights(d_dst)
        r.run_until_idle(timeout=120)
        f_sync.result()
        f_sum = r.submit_queued_operation(
            api.make_op(dst, api.Op.FORWARD, 0, stored_sum=True))
        r.run_until_idle(timeout=120)
        n = (mb << 20) // 4
        assert f_sum.result()["stored_sum"] == float(n * (n - 1) // 2)
    finally:
        r.close_processes()
    assert my_shm_segments() == []


@needs_shm
def test_child_crash_mid_sync_with_shm_in_flight():
    """The robustness satellite, shm edition: the TARGET child dies inside
    ``_store`` while the source child's descriptors are in flight. The
    sync op fails, its dependents poison, the source group keeps serving,
    the completed-op billing mirror survives, and respawn leaves zero
    /dev/shm residue from the dead incarnation."""
    r = Router(process_plane=True, proc_wpg_factory=CRASH_STORE)
    src = api.DeploymentSpec(deployment_id="src0", job_id="jobS",
                             model_name="stub", role="train",
                             overrides=(("sync_mb", 4),))
    dst = api.DeploymentSpec(deployment_id="dst0", job_id="jobS",
                             model_name="stub", role="rollout")
    try:
        r.create_deployment(src, group_id=0)
        r.create_deployment(dst, group_id=1)
        # a completed op on the doomed group: its billing must survive
        f_pre = r.submit_queued_operation(
            api.make_op(dst, api.Op.FORWARD, 0, sleep_s=0.01))
        r.run_until_idle(timeout=120)
        assert f_pre.result()["seconds"] >= 0.01
        pre_log = list(r.wpgs["dst0"].exec_log)
        d_src, d_dst = api.Deployment(src, r), api.Deployment(dst, r)
        f_sync = d_src.sync_weights(d_dst)
        f_dep = r.submit_queued_operation(
            api.make_op(dst, api.Op.FORWARD, 1,
                        prerequisites=(f_sync,)))
        r.run_until_idle(timeout=120)
        with pytest.raises((RuntimeError, GroupProcessError),
                           match="worker process died"):
            f_sync.result()
        with pytest.raises(RuntimeError, match="prerequisite"):
            f_dep.result()
        assert r.process_health() == {0: True, 1: False}
        assert list(r.wpgs["dst0"].exec_log) == pre_log   # billing conserved
        # the source group survived its peer's death and still serves
        f_ok = r.submit_queued_operation(
            api.make_op(src, api.Op.FORWARD, 2))
        r.run_until_idle(timeout=120)
        assert f_ok.result()["op"] == "forward"
        dead_prefix = f"pxl{os.getpid()}g1s1"
        assert r.respawn_dead_groups() == [1]
        # the dead incarnation left nothing behind in /dev/shm
        assert not [n for n in my_shm_segments()
                    if n.startswith(dead_prefix)]
        f2 = r.submit_queued_operation(
            api.make_op(dst, api.Op.FORWARD, 3))
        r.run_until_idle(timeout=120)
        assert f2.result()["op"] == "forward"
    finally:
        r.close_processes()
    assert my_shm_segments() == []


@needs_shm
def test_migrate_importer_death_cleans_spills_and_segments():
    """Killing the importing child mid-migrate with shm descriptors (and
    forced spill files) in flight: the op raises, the source keeps sole
    ownership of the state, the transfer's ``export__`` spills are
    deleted, and teardown leaves no /dev/shm residue."""
    tiny = (("num_layers", 2), ("d_model", 32), ("num_heads", 4),
            ("num_kv_heads", 2), ("head_dim", 8), ("d_ff", 64),
            ("vocab_size", 64), ("tie_embeddings", True))
    r = Router(process_plane=True, shm_threshold=1024)
    train = api.DeploymentSpec(deployment_id="train0", job_id="jobA",
                               model_name="qwen2-0.5b", role="train",
                               overrides=tiny)
    other = api.DeploymentSpec(deployment_id="other0", job_id="jobB",
                               model_name="qwen2-0.5b", role="train",
                               overrides=tiny)
    try:
        r.create_deployment(train, group_id=0)
        r.create_deployment(other, group_id=1)
        f = r.submit_queued_operation(api.make_op(train, api.Op.INIT, 0))
        r.run_until_idle(timeout=280)
        assert f.result()["params"] > 0
        bytes_before = r.state_managers[0].job_bytes("jobA:train0")
        assert bytes_before > 0
        os.kill(r.group_procs[1].pid(), signal.SIGKILL)
        r.group_procs[1]._proc.join(timeout=30)
        with pytest.raises(GroupProcessError, match="worker process died"):
            # tiny max_inline forces the SPILL tier: its cleanup path
            r.state_managers[0].migrate("jobA:train0", r.state_managers[1],
                                        max_inline_bytes=2048)
        # source still owns the state, transfer spills are gone
        assert r.state_managers[0].job_bytes("jobA:train0") == bytes_before
        src_node = r.group_procs[0].node_id
        assert glob.glob(f"/tmp/plexrl_{src_node}/export__*") == []
        with pytest.raises(GroupProcessError, match="worker process died"):
            # default path: everything inline as shm DESCRIPTORS in flight
            r.state_managers[0].migrate("jobA:train0", r.state_managers[1])
        assert r.state_managers[0].job_bytes("jobA:train0") == bytes_before
        # the export really rode the source child's segment pool (released
        # segments persist in its free list until the child exits)
        assert [n for n in my_shm_segments()
                if n.startswith(f"pxl{os.getpid()}g0s1c")]
        assert r.respawn_dead_groups() == [1]
        assert r.process_health() == {0: True, 1: True}
    finally:
        r.close_processes()
    assert my_shm_segments() == []


def test_respawn_sweeps_orphaned_spill_files():
    """A crash between export and import orphans the transaction's spill
    files; respawn's sweep removes them — and ONLY them (regular
    disk-tier state files are untouched)."""
    r, specs = make_proc_router(n_groups=1)
    spill_dir = f"/tmp/plexrl_{r.group_procs[0].node_id}"
    os.makedirs(spill_dir, exist_ok=True)
    orphan = os.path.join(spill_dir, "export__deadbeef__jobX__w.npy")
    keeper = os.path.join(spill_dir, "jobX__w.npy")
    try:
        for p in (orphan, keeper):
            with open(p, "wb") as fh:
                fh.write(b"\x93NUMPY")
        f_bad = r.submit_queued_operation(
            api.make_op(specs[0], api.Op.FORWARD, 0, crash=True))
        r.run_until_idle(timeout=120)
        with pytest.raises(RuntimeError, match="worker process died"):
            f_bad.result()
        assert r.respawn_dead_groups() == [0]
        assert not os.path.exists(orphan)     # transaction orphan swept
        assert os.path.exists(keeper)         # real disk-tier state kept
    finally:
        r.close_processes()
        for p in (orphan, keeper):
            if os.path.exists(p):
                os.unlink(p)


def test_import_rollback_unlinks_spills(tmp_path):
    """Satellite bugfix: a failing import_state deletes the transfer's
    spill files during rollback instead of leaking them (the transfer is
    over either way — nobody will read them again)."""
    src = StateManager(node_id="src", disk_dir=str(tmp_path / "src"))
    dst = StateManager(node_id="dst", disk_dir=str(tmp_path / "dst"))
    big = np.arange(4096, dtype=np.float32)
    src.register("jobA:dep0", {"w": big, "v": big * 2}, Tier.HOST)
    payload = src.export_state("jobA:dep0", max_inline_bytes=1024)
    assert len(payload["spills"]) == 2
    # spill names are transaction-scoped: two exports never collide
    payload2 = src.export_state("jobA:dep0", max_inline_bytes=1024)
    assert set(payload["spills"]).isdisjoint(payload2["spills"])
    for p in payload["spills"] + payload2["spills"]:
        assert os.path.exists(p)
    # corrupt the tail of the payload so the import fails mid-stage
    payload["entries"].append(
        {"key": "jobA:dep0/params/ghost", "nbytes": 8, "version": 0,
         "tier": int(Tier.HOST), "is_bf16": False, "spec": None,
         "path": str(tmp_path / "missing.npy"), "data": None})
    with pytest.raises(Exception):
        dst.import_state(payload)
    assert dst.keys_for("jobA:dep0") == []            # rolled back
    for p in payload["spills"]:
        assert not os.path.exists(p)                  # …and spills gone
    # the untouched second export still imports cleanly
    assert dst.import_state(payload2) == payload2["bytes"]
    for p in payload2["spills"]:
        assert not os.path.exists(p)
