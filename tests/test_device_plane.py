"""Device plane: mesh-slice carving, device-aware state residency,
cross-mesh migration (bit-identity, rollback), the bounded exec log, and —
under XLA_FLAGS=--xla_force_host_platform_device_count=8 — the e2e
disjoint-slice acceptance path."""
import os
import types

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import api
from repro.core.cluster import BillingRecord, PlexCluster
from repro.core.controller import JobConfig
from repro.core.state_manager import StateManager, Tier, _spec_divides
from repro.core.worker import ExecLog
from repro.launch import compile_cache
from repro.launch.mesh import DevicePlane, hw_peaks, make_local_mesh

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

TINY = (("num_layers", 2), ("d_model", 32), ("num_heads", 4),
        ("num_kv_heads", 2), ("head_dim", 8), ("d_ff", 64),
        ("vocab_size", 64), ("tie_embeddings", True))


# ------------------------------------------------------------ DevicePlane

def test_carve_slices_disjoint_and_cover():
    plane = DevicePlane()
    slices = plane.carve(n_groups=2)
    seen = set()
    for s in slices:
        ids = set(s.device_ids())
        assert ids.isdisjoint(seen), "slices must be disjoint"
        seen |= ids
        assert s.mesh.axis_names == ("data", "model")
        assert s.mesh.shape["model"] == s.n_devices
    assert seen <= {d.id for d in jax.devices()}


def test_acquire_is_idempotent_and_deterministic():
    a, b = DevicePlane(slice_size=max(1, N_DEV // 2)), \
        DevicePlane(slice_size=max(1, N_DEV // 2))
    for plane in (a, b):
        s0 = plane.slice_for_group(0)
        assert plane.slice_for_group(0) is s0     # idempotent per group
    # identical acquisition order -> identical slice assignment (the
    # VirtualClock replay contract: mesh binding is clock-free)
    assert a.slice_for_group(1).index == b.slice_for_group(1).index
    assert a.domains() == b.domains()


def test_release_returns_lease():
    plane = DevicePlane()
    s0 = plane.slice_for_group(0)
    plane.release(0)
    assert plane.slice_index(0) is None
    # the freed slice is the lowest-index free slice again
    assert plane.slice_for_group(7).index == s0.index


def test_oversubscribed_groups_share_least_loaded_slice():
    plane = DevicePlane(slice_size=N_DEV)    # exactly one slice
    s0 = plane.slice_for_group(0)
    s1 = plane.slice_for_group(1)            # no free slice: shared
    assert s0 is s1


def test_make_local_mesh_validates_device_count():
    with pytest.raises(ValueError) as ei:
        make_local_mesh(data=N_DEV + 1, model=1)
    msg = str(ei.value)
    assert "xla_force_host_platform_device_count" in msg
    assert str(N_DEV + 1) in msg


def test_hw_peaks_keyed_by_device_kind():
    assert hw_peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        hw_peaks("cpu")


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at a fixed, git-ignored .jax_cache/ in the checkout root. A process over
    several chips turns the cache off."""
    before = jax.config.jax_compilation_cache_dir
    enabled = jax.config.jax_enable_compilation_cache
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(root, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
        # a process over several chips reads and writes no cache at all
        assert compile_cache.use_compile_cache(n_chips=4) is None
        assert not jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_enable_compilation_cache", enabled)


def test_spec_divides_checks_every_sharded_dim():
    mesh = types.SimpleNamespace(shape={"data": 2, "model": 4})
    assert _spec_divides((8, 6), P("model"), mesh)
    assert not _spec_divides((6, 8), P("model"), mesh)
    assert not _spec_divides((8, 6), P(None, "model"), mesh)
    assert _spec_divides((8, 6), P(("data", "model")), mesh)
    assert not _spec_divides((4, 6), P(("data", "model")), mesh)
    assert _spec_divides((3, 5), P(), mesh)


# ---------------------------------------------------------------- ExecLog

def test_exec_log_ring_bounds_memory_and_preserves_cursors():
    """Churn regression: a week-long serve plane must not leak one tuple
    per op — the ring trims, while absolute-offset cursors keep billing
    exact across trims."""
    log = ExecLog(maxlen=16)
    cursor, billed = 0, 0.0
    for i in range(16 * 3):
        log.append(("op", 1.0))
        if i % 10 == 9:                      # bill faster than the trim
            new, cursor = log.since(cursor)
            billed += sum(dt for _, dt in new)
    new, cursor = log.since(cursor)
    billed += sum(dt for _, dt in new)
    assert len(log) <= 16                    # memory bounded
    assert log.total() == 48                 # absolute count preserved
    assert billed == 48.0                    # every op billed exactly once
    assert cursor == 48
    # legacy consumers: iteration / indexing cover the retained window
    assert list(log) == [("op", 1.0)] * len(log)
    assert log[0] == ("op", 1.0)


def test_cluster_billing_consumes_ring_cursors():
    c = PlexCluster(n_groups=1)
    spec = api.DeploymentSpec(deployment_id="jobR-d", job_id="jobR",
                              model_name="qwen2-0.5b", role="train")

    class _W:
        def __init__(self):
            self.spec = spec
            self.exec_log = ExecLog(maxlen=4)

    w = _W()
    c.billing["jobR"] = BillingRecord("jobR")
    for _ in range(12):                      # 3x the ring size
        w.exec_log.append(("op", 0.5))
        with c._bill_lock:
            c._bill_from_logs(extra_wpgs={"jobR-d": w})
    assert c.billing["jobR"].busy_seconds == pytest.approx(6.0)
    assert len(w.exec_log) <= 4


# ------------------------------------------- cross-mesh StateManager moves

def _two_slice_sms():
    plane = DevicePlane(slice_size=max(1, N_DEV // 2))
    src = StateManager(node_id="src", mesh_slice=plane.slice_for_group(0))
    dst = StateManager(node_id="dst", mesh_slice=plane.slice_for_group(1))
    return src, dst


def _sharded_tree(mesh):
    rng = np.random.RandomState(0)
    host = {
        "w": rng.rand(8, N_DEV * 4).astype(np.float32),
        "b": rng.rand(32).astype(np.float32),
        "scale": rng.rand(4, 4).astype(np.float32),
    }
    specs = {"w": P(None, "model"), "b": P(), "scale": P()}
    dev = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
           for k, v in host.items()}
    return host, dev


def test_cross_slice_migrate_bit_identical_with_host_tier_entry():
    src, dst = _two_slice_sms()
    host, dev = _sharded_tree(src.mesh_slice.mesh)
    src.register("job:dep", dev, Tier.DEVICE, "params")
    mom = {k: np.zeros_like(v) for k, v in host.items()}
    src.register("job:dep", {"mu": mom}, Tier.DEVICE, "opt")
    # a host-tier (offloaded) entry rides along
    src.offload(["job:dep/params/b"], Tier.HOST)
    assert src.entries["job:dep/params/b"].tier == Tier.HOST

    tmpl = {k: np.zeros_like(v) for k, v in host.items()}
    before = jax.tree.map(np.asarray, src.gather("job:dep", tmpl, "params"))
    moved = src.migrate("job:dep", dst)
    assert moved > 0 and not src.keys_for("job:dep")
    assert src.last_migrate["bytes"] == moved
    assert src.last_migrate["cross_mesh"] == (N_DEV >= 2)

    after = jax.tree.map(np.asarray, dst.gather("job:dep", tmpl, "params"))
    for k in host:
        np.testing.assert_array_equal(before[k], after[k])
    # device-tier entries landed RESHARDED onto the destination slice
    dst_ids = set(dst.mesh_slice.device_ids())
    for key, e in dst.entries.items():
        if e.tier == Tier.DEVICE:
            arr_ids = {d.id for d in e.ref.devices()}
            assert arr_ids <= dst_ids, key
    # the sharded leaf kept its PartitionSpec across the reshard
    w = dst.entries["job:dep/params/w"]
    assert w.tier == Tier.DEVICE
    assert tuple(w.ref.sharding.spec) == (None, "model")


def test_mid_migration_failure_rolls_back():
    src, dst = _two_slice_sms()
    host, dev = _sharded_tree(src.mesh_slice.mesh)
    src.register("job:dep", dev, Tier.DEVICE, "params")
    keys_before = set(src.keys_for("job:dep"))
    tmpl = {k: np.zeros_like(v) for k, v in host.items()}
    before = jax.tree.map(np.asarray, src.gather("job:dep", tmpl, "params"))

    class _FailingEntries(dict):
        inserts = 0

        def __setitem__(self, k, v):
            type(self).inserts += 1
            if type(self).inserts == 2:
                raise RuntimeError("injected mid-migration failure")
            super().__setitem__(k, v)

    failing = _FailingEntries()
    failing.update(dst.entries)
    dst.entries = failing
    with pytest.raises(RuntimeError, match="injected"):
        src.migrate("job:dep", dst)
    # source untouched (all tiers), destination holds no partial copies
    assert set(src.keys_for("job:dep")) == keys_before
    again = jax.tree.map(np.asarray, src.gather("job:dep", tmpl, "params"))
    for k in host:
        np.testing.assert_array_equal(before[k], again[k])
    assert not [k for k in dst.entries if k.startswith("job:dep/")]


def test_prefetch_restores_recorded_spec_on_own_slice():
    src, _ = _two_slice_sms()
    host, dev = _sharded_tree(src.mesh_slice.mesh)
    keys = src.register("job:dep", dev, Tier.DEVICE, "params")
    src.offload(keys, Tier.HOST)
    src.prefetch(keys)
    w = src.entries["job:dep/params/w"]
    assert w.tier == Tier.DEVICE
    assert tuple(w.ref.sharding.spec) == (None, "model")
    ids = {d.id for d in w.ref.sharding.mesh.devices.flat}
    assert ids == set(src.mesh_slice.device_ids())


# ----------------------------------------------- e2e acceptance (8 devices)

def _job(job_id, seed, steps=1):
    return JobConfig(job_id=job_id, model_name="qwen2-0.5b", steps=steps,
                     batch_size=4, group_size=2, max_new_tokens=4,
                     seq_len=24, overrides=TINY, seed=seed)


def _sharding_device_ids(shardings):
    return {d.id
            for s in jax.tree.leaves(
                shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
            for d in s.mesh.devices.flat}


@multi_device
def test_e2e_disjoint_slices_and_cross_slice_live_migration():
    """Two real-model jobs on groups holding DISJOINT mesh slices; one is
    live-migrated across slices with params bit-identical and billing
    conserved."""
    c = PlexCluster(n_groups=2, devices_per_group=4)
    c.add_job(_job("jobM1", 1), group_id=0)
    c.add_job(_job("jobM2", 2), group_id=1)
    c.run(interleave=True)

    assert c.router.mesh_domains() == {0: 0, 1: 1}
    w1 = c.router.wpgs["jobM1-train"]
    w2 = c.router.wpgs["jobM2-train"]
    ids1 = _sharding_device_ids(w1.param_shardings())
    ids2 = _sharding_device_ids(w2.param_shardings())
    assert len(ids1) == 4 and len(ids2) == 4
    assert ids1.isdisjoint(ids2), "groups must execute on disjoint hardware"
    # the WPGs' live params actually reside on their group's slice
    for wpg, ids in ((w1, ids1), (w2, ids2)):
        sm = wpg.sm
        for k in sm.keys_for(wpg.job_prefix, "params"):
            e = sm.entries[k]
            if e.tier == Tier.DEVICE:
                arr_ids = {d.id for d in e.ref.devices()}
                assert arr_ids <= ids

    before = jax.tree.map(lambda a: np.asarray(jax.device_get(a)),
                          w1.params())
    with c._bill_lock:
        c._bill_from_logs()
    busy_before = c.billing["jobM1"].busy_seconds
    assert busy_before > 0.0

    moved = c.reassign_job("jobM1", 1)
    assert moved > 0
    assert c.router.group_of["jobM1-train"] == 1
    assert c.router.migrate_log[-1]["cross_mesh"] is True

    after = w1.params()
    flat_b = jax.tree.leaves(before)
    flat_a = jax.tree.leaves(after)
    assert len(flat_b) == len(flat_a)
    for b, a in zip(flat_b, flat_a):
        np.testing.assert_array_equal(
            np.asarray(b, np.float32),
            np.asarray(jax.device_get(a), np.float32))
    # migrated state now lives on group 1's slice
    ids_after = {d.id
                 for leaf in flat_a if isinstance(leaf, jax.Array)
                 for d in leaf.devices()}
    assert ids_after and ids_after <= ids2
    # billing conserved: migration itself bills nothing, cursors survive
    with c._bill_lock:
        c._bill_from_logs()
    assert c.billing["jobM1"].busy_seconds == pytest.approx(busy_before)
