"""StateManager: residency tiers, canonical dedup, materialisation, host
optimizer, migration."""
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import state_manager as sm_lib
from repro.core.state_manager import StateManager, Tier
from repro.core.telemetry import Counters
from repro.launch.mesh import DevicePlane
from repro.train import optimizer as opt


def _tree(seed=0, dtype=jnp.float32):
    k = jax.random.PRNGKey(seed)
    return {
        "w1": jax.random.normal(k, (8, 16), dtype),
        "nested": {"w2": jnp.ones((4,), dtype)},
    }


def test_register_offload_prefetch_roundtrip(tmp_path):
    sm = StateManager(disk_dir=str(tmp_path))
    tree = _tree()
    keys = sm.register("job", tree)
    assert sm.usage()["DEVICE"] > 0
    sm.offload(keys, Tier.HOST)
    assert sm.usage()["DEVICE"] == 0 and sm.usage()["HOST"] > 0
    sm.offload(keys, Tier.DISK)
    assert sm.usage()["HOST"] == 0 and sm.usage()["DISK"] > 0
    sm.prefetch(keys)
    assert sm.usage()["DEVICE"] > 0
    out = sm.gather("job", jax.tree.map(lambda x: x, tree))
    np.testing.assert_allclose(np.asarray(out["w1"]), np.asarray(tree["w1"]))


def test_bf16_disk_roundtrip(tmp_path):
    sm = StateManager(disk_dir=str(tmp_path))
    tree = _tree(dtype=jnp.bfloat16)
    keys = sm.register("job", tree)
    sm.offload(keys, Tier.DISK)
    sm.prefetch(keys)
    out = sm.gather("job", tree)
    assert out["w1"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["w1"], np.float32), np.asarray(tree["w1"], np.float32))


def test_canonical_dedup_refcount(tmp_path):
    sm = StateManager(disk_dir=str(tmp_path))
    tree = _tree()
    k1 = sm.register("job", tree)            # replica 1
    bytes_once = sm.usage()["DEVICE"]
    k2 = sm.register("job", tree)            # data-parallel replica 2
    assert k1 == k2
    assert sm.usage()["DEVICE"] == bytes_once    # deduplicated (§4.5.2)
    sm.unregister(k2)
    assert sm.usage()["DEVICE"] == bytes_once    # still referenced
    sm.unregister(k1)
    assert sm.usage()["DEVICE"] == 0


def test_capacity_eviction_lru(tmp_path):
    tree = {"a": jnp.ones((1024,), jnp.float32),
            "b": jnp.ones((1024,), jnp.float32)}
    sm = StateManager(disk_dir=str(tmp_path), device_capacity=5000)
    sm.register("job", tree)
    # 8KB registered > 5000B capacity -> one entry must have been evicted
    assert sm.usage()["DEVICE"] <= 5000
    assert sm.usage()["HOST"] > 0


def test_materialize_checkpoint_from_offloaded(tmp_path):
    sm = StateManager(disk_dir=str(tmp_path / "disk"))
    tree = _tree()
    keys = sm.register("job", tree)
    sm.offload(keys, Tier.HOST)               # checkpoint despite offload
    path = sm.materialize_checkpoint("job", tree, str(tmp_path / "ckpt"))
    from repro.train import checkpoint as ckpt
    restored, meta = ckpt.restore(path, tree)
    np.testing.assert_allclose(np.asarray(restored["w1"]),
                               np.asarray(tree["w1"]))
    assert meta["job_id"] == "job"


def test_host_optimizer_matches_device_adamw(tmp_path):
    """§4.5.4 CPU optimizer == the jitted AdamW (same hyperparams, no wd)."""
    sm = StateManager(disk_dir=str(tmp_path))
    params = _tree(seed=1)
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.1), params)
    sm.register("job", params)
    sm.host_optimizer_step("job", grads, params, lr=1e-2)
    host_out = sm.gather("job", params)

    cfg = opt.AdamWConfig(lr=1e-2, grad_clip=0.0, warmup_steps=0,
                          weight_decay=0.0)
    state = opt.init(params, cfg)
    dev_out, _, _ = opt.update(grads, state, params, cfg)
    for k in ("w1",):
        np.testing.assert_allclose(np.asarray(host_out[k]),
                                   np.asarray(dev_out[k]), rtol=1e-5,
                                   atol=1e-6)


def test_migration_moves_all_state(tmp_path):
    src = StateManager(node_id="src", disk_dir=str(tmp_path / "a"))
    dst = StateManager(node_id="dst", disk_dir=str(tmp_path / "b"))
    tree = _tree()
    src.register("job", tree)
    moved = src.migrate("job", dst)
    assert moved > 0
    assert not src.keys_for("job")
    out = dst.gather("job", tree)
    np.testing.assert_allclose(np.asarray(out["nested"]["w2"]),
                               np.asarray(tree["nested"]["w2"]))


def test_sync_weights_resharding_cast(tmp_path):
    sm = StateManager(disk_dir=str(tmp_path))
    tree = _tree()
    sm.register("job", tree)
    synced = sm.sync_weights("job", tree, dtype=jnp.bfloat16)
    assert synced["w1"].dtype == jnp.bfloat16


def _mixed_tree():
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    return {"w": jax.random.normal(k1, (16, 8), jnp.bfloat16),
            "mu": jax.random.normal(k2, (16, 8), jnp.float32),
            "step": jnp.asarray(3, jnp.int32)}


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16) if x.dtype == jnp.bfloat16 else x


@pytest.mark.parametrize("tier,lands", [(Tier.DEVICE, Tier.DEVICE),
                                        (Tier.HOST, Tier.HOST),
                                        (Tier.DISK, Tier.HOST)],
                         ids=lambda t: t.name)
def test_migrate_keeps_each_entrys_bits_and_tier(tmp_path, tier, lands):
    """Onto a destination with a mesh slice, a DEVICE entry lands on the
    device and a HOST entry on the host; a DISK entry is read back and
    lands on the host. Every entry keeps its bits."""
    sl = DevicePlane().slice_for_group(0)
    src = StateManager(node_id="src", disk_dir=str(tmp_path / "src"),
                       mesh_slice=sl)
    dst = StateManager(node_id="dst", disk_dir=str(tmp_path / "dst"),
                       mesh_slice=sl)
    tree = _mixed_tree()
    keys = src.register("job", tree)
    if tier != Tier.DEVICE:
        src.offload(keys, tier)
    src.migrate("job", dst)
    assert not src.keys_for("job")
    assert {dst.entries[k].tier for k in keys} == {lands}
    out = dst.gather("job", tree)
    for name in tree:
        assert np.asarray(out[name]).dtype == tree[name].dtype
        np.testing.assert_array_equal(_bits(out[name]), _bits(tree[name]))


def _spy_to_host(monkeypatch, before=lambda: None):
    """Record every batched device-to-host call of the state manager."""
    calls = []
    real = sm_lib._to_host

    def spy(srcs, slots):
        calls.append((list(srcs), list(slots)))
        before()
        return real(srcs, slots)

    monkeypatch.setattr(sm_lib, "_to_host", spy)
    return calls


def test_offload_issues_every_copy_before_reading_any(tmp_path, monkeypatch):
    """One offload hands every device leaf to one batched copy, and reads
    none of them on its own (no per-leaf ``device_get``)."""
    from jax._src.array import ArrayImpl
    sm = StateManager(disk_dir=str(tmp_path))
    keys = sm.register("job", _mixed_tree())
    refs = [sm.entries[k].ref for k in keys]
    value = ArrayImpl._value
    read = []
    monkeypatch.setattr(ArrayImpl, "_value", property(
        lambda self: read.append(id(self)) or value.fget(self)))
    calls = _spy_to_host(monkeypatch)
    sm.offload(keys, Tier.HOST)
    assert len(calls) == 1
    assert [id(x) for x in calls[0][0]] == [id(x) for x in refs]
    assert not set(read) & {id(x) for x in refs}
    assert all(sm.entries[k].tier == Tier.HOST for k in keys)


@pytest.mark.parametrize("tier", [Tier.HOST, Tier.DISK], ids=lambda t: t.name)
def test_mixed_tree_round_trips_bit_for_bit(tmp_path, tier):
    sm = StateManager(disk_dir=str(tmp_path))
    tree = _mixed_tree()
    keys = sm.register("job", tree)
    sm.offload(keys, tier)
    assert sm.usage()["DEVICE"] == 0 and sm.usage()[tier.name] > 0
    sm.prefetch(keys)
    assert all(sm.entries[k].tier == Tier.DEVICE for k in keys)
    out = sm.gather("job", tree)
    for name in tree:
        assert out[name].dtype == tree[name].dtype
        assert out[name].sharding.memory_kind == "device"
        np.testing.assert_array_equal(_bits(out[name]), _bits(tree[name]))


@pytest.mark.parametrize("tier", [Tier.HOST, Tier.DISK], ids=lambda t: t.name)
def test_key_unregistered_mid_offload_is_skipped(tmp_path, monkeypatch, tier):
    """A teardown that unregisters a key after the offload collected it and
    before its copy lands: the key is skipped, the rest move, and the
    counters count only what moved."""
    tel = Counters()
    sm = StateManager(disk_dir=str(tmp_path), telemetry=tel)
    keys = sm.register("job", _mixed_tree())
    gone, kept = keys[0], keys[1:]
    _spy_to_host(monkeypatch, before=lambda: sm.unregister([gone]))
    sm.offload(keys, tier)
    assert gone not in sm.entries
    assert all(sm.entries[k].tier == tier for k in kept)
    snap = tel.snapshot()
    assert snap["switch.offload_leaves"] == len(kept)
    assert snap["switch.offload_bytes"] == sum(sm.entries[k].nbytes
                                               for k in kept)
    assert sorted(os.listdir(tmp_path)) == (
        sorted(k.replace("/", "__") + ".npy" for k in kept)
        if tier == Tier.DISK else [])


def test_offload_leaves_untouched_entries_below_target(tmp_path):
    """Entries already at or below the target tier do not move or count."""
    tel = Counters()
    sm = StateManager(disk_dir=str(tmp_path), telemetry=tel)
    keys = sm.register("job", _mixed_tree())
    sm.offload(keys[:1], Tier.DISK)
    sm.offload(keys, Tier.HOST)
    assert sm.entries[keys[0]].tier == Tier.DISK
    assert all(sm.entries[k].tier == Tier.HOST for k in keys[1:])
    assert tel.snapshot()["switch.offload_leaves"] == len(keys)


def test_next_offload_lands_in_the_pinned_buffer_it_loaded_from(
        tmp_path, monkeypatch):
    """A HOST entry holds a pinned-host buffer; the load back keeps it, and
    the key's next offload hands it to the copy as that leaf's destination,
    also after the job re-registered its state (as a WPG does after every
    update)."""
    sm = StateManager(disk_dir=str(tmp_path))
    tree = _mixed_tree()
    keys = sm.register("job", tree)
    sm.register("job", {"count": jnp.zeros((), jnp.int32)}, prefix="opt")
    sm.offload(keys, Tier.HOST)
    pinned = [sm.entries[k].ref for k in keys]
    assert all(x.sharding.memory_kind == "pinned_host" for x in pinned)
    sm.prefetch(keys)
    sm.unregister(keys)
    keys = sm.register("job", jax.tree.map(lambda x: x + 1, tree))
    calls = _spy_to_host(monkeypatch)
    sm.offload(keys, Tier.HOST)
    assert [id(s) for s in calls[0][1]] == [id(x) for x in pinned]
    assert not sm._slots
    out = sm.gather("job", tree)
    for name in tree:
        np.testing.assert_array_equal(_bits(out[name]),
                                      _bits(tree[name] + 1))


def test_a_buffer_is_reused_under_another_spec_of_the_same_layout(
        tmp_path, monkeypatch):
    """A step may return a leaf under another PartitionSpec than the one it
    was loaded with; on the same per-device layout its buffer still takes
    the copy, and a buffer of another shape does not."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    sm = StateManager(disk_dir=str(tmp_path))
    x = jnp.arange(32.0).reshape(4, 8)
    keys = sm.register("job", {"w": put(x, P(None, "model")),
                               "v": put(x, P())})
    sm.register("job", {"count": jnp.zeros((), jnp.int32)}, prefix="opt")
    sm.offload(keys, Tier.HOST)
    sm.prefetch(keys)
    slots = [sm._slots[k] for k in keys]
    sm.unregister(keys)
    keys = sm.register("job", {"w": put(x + 1, P()),
                               "v": put(x.T + 1, P())})
    calls = _spy_to_host(monkeypatch)
    sm.offload(keys, Tier.HOST)
    given = dict(zip(keys, calls[0][1]))
    assert given["job/params/w"] is slots[keys.index("job/params/w")]
    assert given["job/params/v"] is None          # another shape


def test_pinned_buffers_go_with_the_jobs_last_entry(tmp_path):
    sm = StateManager(disk_dir=str(tmp_path))
    keys = sm.register("job", _mixed_tree())
    other = sm.register("other", _mixed_tree())
    sm.offload(keys + other, Tier.HOST)
    sm.prefetch(keys + other)
    assert set(sm._slots) == set(keys + other)
    sm.unregister(keys[:1])
    assert set(sm._slots) == set(keys + other)
    sm.unregister(keys[1:])
    assert set(sm._slots) == set(other)


def test_every_host_consumer_gets_numpy(tmp_path):
    """Offloaded HOST entries hold pinned-host jax arrays; gather,
    migrate, host_optimizer_step and the DISK path all see numpy with the
    offloaded bits."""
    tree = _mixed_tree()
    params = {"w": tree["w"], "mu": tree["mu"]}

    def offloaded(name):
        sm = StateManager(node_id=name, disk_dir=str(tmp_path / name))
        keys = sm.register("job", tree)
        sm.offload(keys, Tier.HOST)
        assert all(isinstance(sm.entries[k].ref, jax.Array) for k in keys)
        return sm, keys

    sm, keys = offloaded("gather")
    out = sm.gather("job", tree)
    for name in tree:
        assert type(out[name]) is np.ndarray
        np.testing.assert_array_equal(_bits(out[name]), _bits(tree[name]))

    sm, keys = offloaded("migrate")
    dst = StateManager(node_id="dst", disk_dir=str(tmp_path / "dst"))
    sm.migrate("job", dst)
    assert all(type(e.ref) is np.ndarray for e in dst.entries.values())
    np.testing.assert_array_equal(_bits(dst.gather("job", tree)["w"]),
                                  _bits(tree["w"]))

    sm, keys = offloaded("hostopt")
    grads = jax.tree.map(jnp.zeros_like, params)
    sm.host_optimizer_step("job", grads, params)
    assert all(type(sm.entries[f"job/params/{k}"].ref) is np.ndarray
               for k in params)

    sm, keys = offloaded("disk")
    sm.offload(keys, Tier.DISK)
    assert all(sm.entries[k].ref is None for k in keys)
    sm.prefetch(keys)
    out = sm.gather("job", tree)
    for name in tree:
        np.testing.assert_array_equal(_bits(out[name]), _bits(tree[name]))


def test_gather_sees_whole_trees_while_another_thread_moves_them(tmp_path):
    """A client callback gathers a job's state while the dispatch thread
    re-registers it (after an update) and switches it out and back in:
    every gathered tree is whole, and one of the versions stored."""
    sm = StateManager(disk_dir=str(tmp_path))
    versions = [{f"w{i}": jnp.full((4, 4), float(v)) for i in range(16)}
                for v in range(3)]
    sm.register("job", versions[0])
    errors, gathered = [], []
    moving = threading.Event()

    def mover():
        try:
            for i in range(150):
                sm.reregister("job", versions[i % 3])
                sm.register("other", versions[0])
                sm.offload(sm.keys_for("job"), Tier.HOST)
                sm.unregister(sm.keys_for("other"))
                sm.prefetch(sm.keys_for("job"))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)
        finally:
            moving.set()

    def reader():
        try:
            while not moving.is_set():
                out = sm.gather("job", versions[0])
                vals = {float(np.asarray(x)[0, 0]) for x in out.values()}
                gathered.append(vals)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=f) for f in (mover, reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert gathered
    # every leaf of a gathered tree came from the same stored version
    assert all(len(vals) == 1 for vals in gathered), gathered
