"""Model State Manager: per-node authority over tensor residency (paper §4.5).

Three-tier hierarchy adapted to this runtime:

    DEVICE  — accelerator memory (jax arrays, possibly sharded)
    HOST    — canonicalised host buffers: pinned-host jax arrays where an
              offload put them, numpy where the host produced them
              (``np.asarray(entry.ref)`` gives numpy either way)
    DISK    — .npz spill files ("NVMe", via repro.train.checkpoint shards)

Key mechanisms reproduced:
- §4.5.1 hierarchical residency with scheduler-directed prefetch/offload and
  capacity-aware eviction (device -> host -> disk).
- §4.5.2 canonicalised offloaded state: tensors are indexed by logical key
  (repro.models.common.canonical_flat), deduplicating data-parallel replicas
  and decoupling storage from process layout.
- §4.5.3 materialisation (checkpoints from managed state), weight sync with
  on-the-fly zero-redundancy resharding (each target fetches only the slices
  its layout needs), cross-node migration.
- §4.5.4 off-critical-path work: a host-resident AdamW step (the CPU
  optimizer of ZeRO-offload) over canonical host state.

Both directions move a call's leaves as one batch: an offload issues every
device-to-host copy before it waits on any (one jitted program of
asynchronous copies into pinned host memory) and returns once all have
landed; a load issues every host-to-device copy and returns without
waiting. A load keeps each leaf's pinned buffer (``StateManager._slots``,
by key, across re-registration), and the key's next offload lands in it
(donated): after a job's first offload, a switch neither allocates nor
pins host memory. Transfer timings feed a bandwidth estimate; HRRS pulls
its C_setup estimates from ``load_time_estimate`` /
``offload_time_estimate``, so the load's is an enqueue time and the
offload's the transfer's. With the Router's counters attached
(``telemetry``), each tier move opens a ``plexrl.switch.offload`` /
``plexrl.switch.load`` span and adds ``switch.offload_s`` /
``switch.offload_bytes`` / ``switch.offload_leaves`` and
``switch.load_bytes``, and ``switch.load_s`` once every loaded array is
ready on the device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import telemetry as telemetry_lib
from repro.models import common

logger = logging.getLogger(__name__)


class Tier(enum.IntEnum):
    DEVICE = 0
    HOST = 1
    DISK = 2


@dataclasses.dataclass
class Entry:
    key: str                         # canonical logical key (job-scoped)
    tier: Tier
    nbytes: int
    ref: Any = None                  # jax array (DEVICE) / pinned-host
    #   jax array or np array (HOST)
    path: Optional[str] = None       # DISK shard path
    version: int = 0
    refcount: int = 1                # dedup count across logical replicas
    last_touch: float = 0.0
    is_bf16: bool = False            # DISK tier stores bf16 as uint16 views
    spec: Any = None                 # PartitionSpec the DEVICE copy had, so
    #   prefetch/migrate can rebuild the layout on THIS node's mesh slice
    #   (or reshard onto a different slice's mesh — §4.5.3)


def _nbytes(x) -> int:
    return int(np.prod(x.shape)) * x.dtype.itemsize


@functools.partial(jax.jit, donate_argnums=0, keep_unused=True)
def _copy_into(slots, srcs):
    """Copy every leaf of ``srcs`` to pinned host memory in one program: all
    the copies start before any is waited on. A leaf of ``slots`` (or None)
    is a pinned buffer of the same shape and layout, donated to hold it."""
    return [jax.device_put(x, jax.memory.Space.Host) for x in srcs]


def _to_host(srcs: List[jax.Array], slots: List[Any]) -> List[jax.Array]:
    """``srcs`` copied to pinned host memory, once every copy has landed.
    The CPU backend cannot place a program's outputs in host memory (its
    device memory is host memory): there the copies are one batched
    ``device_put`` that reuses no buffer."""
    if not srcs:
        return []
    if next(iter(srcs[0].devices())).platform == "cpu":
        out = jax.device_put(
            srcs, [x.sharding.with_memory_kind("pinned_host") for x in srcs])
    else:
        out = _copy_into(slots, srcs)
    return jax.block_until_ready(out)


def _locked(method):
    """Run the method under the manager's lock (see ``StateManager``)."""
    @functools.wraps(method)
    def run(self, *args, **kwargs):
        with self._lock:
            return method(self, *args, **kwargs)
    return run


def _spec_divides(shape, spec, mesh) -> bool:
    """Whether every dim of ``shape`` divides by the product of the mesh
    axes ``spec`` assigns to it (what a sharded ``device_put`` requires)."""
    if len(spec) > len(shape):
        return False
    for dim, axes in zip(shape, spec):
        if axes is None:
            continue
        names = (axes,) if isinstance(axes, str) else tuple(axes)
        if dim % int(np.prod([mesh.shape[a] for a in names])):
            return False
    return True


class StateManager:
    """One instance per node. Owns every byte of managed model state.

    One lock serialises what adds, drops, moves or reads entries
    (``register``, ``reregister``, ``unregister``, ``offload``,
    ``prefetch``, eviction, ``gather``, ``keys_for``, ``job_bytes``): a job's
    client callback may gather its state while the group's dispatch thread
    switches or updates another job, and sees each entry either before a
    move or after it."""

    def __init__(self, node_id: str = "node0",
                 device_capacity: float = float("inf"),
                 host_capacity: float = float("inf"),
                 disk_dir: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic,
                 mesh_slice=None,
                 telemetry: Optional[telemetry_lib.Counters] = None):
        self.node_id = node_id
        self.device_capacity = device_capacity
        self.host_capacity = host_capacity
        self.disk_dir = disk_dir or os.path.join("/tmp", f"plexrl_{node_id}")
        self.clock = clock
        # the node group's MeshSlice (launch/mesh.py): DEVICE-tier entries
        # live on these devices; None = wherever jax defaults (legacy view)
        self.mesh_slice = mesh_slice
        self.entries: Dict[str, Entry] = {}
        self._lock = threading.RLock()
        self.telemetry = telemetry
        # pinned-host buffers loads came from, by key, for the key's next
        # offload to land in; not counted in HOST, dropped with the job's
        # last entry (WPGs re-register a job's keys after every update)
        self._slots: Dict[str, jax.Array] = {}
        self._bw_estimate: Dict[str, float] = {}   # bytes/s per direction
        self.last_migrate: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------ helpers
    def _tier_bytes(self, tier: Tier) -> int:
        return sum(e.nbytes for e in self.entries.values() if e.tier == tier)

    def usage(self) -> Dict[str, int]:
        return {t.name: self._tier_bytes(t) for t in Tier}

    def _record(self, direction: str, nbytes: int, dt: float):
        if dt > 0 and nbytes > 0:
            bw = nbytes / dt
            old = self._bw_estimate.get(direction)
            self._bw_estimate[direction] = bw if old is None else 0.7 * old + 0.3 * bw

    def _estimate(self, direction: str, nbytes: int, default_bw: float) -> float:
        bw = self._bw_estimate.get(direction, default_bw)
        return nbytes / max(bw, 1.0)

    def _move_span(self, name: str, keys: Sequence[str],
                   moves: Callable[[Entry], bool]):
        """A ``plexrl.<name>`` span over a tier move, opened only where
        some entry of ``keys`` moves (attributes: job, bytes)."""
        todo = [e for k in keys
                if (e := self.entries.get(k)) is not None and moves(e)]
        if not todo:
            return contextlib.nullcontext()
        return telemetry_lib.span(name, job=todo[0].key.split("/", 1)[0],
                                  bytes=sum(e.nbytes for e in todo))

    @staticmethod
    def _leaf_spec(leaf):
        """The PartitionSpec of a device-resident jax array (None for host
        numpy / unsharded arrays)."""
        shd = getattr(leaf, "sharding", None)
        return shd.spec if isinstance(shd, NamedSharding) else None

    def _to_device(self, arr, spec=None):
        """Place a host array onto THIS node's mesh slice, restoring
        ``spec`` when its dims divide over the slice's mesh; a spec that
        does not divide there is logged and the leaf replicated on the
        slice (or put on the default device with no slice)."""
        if self.mesh_slice is not None:
            mesh = self.mesh_slice.mesh
            if spec is not None:
                if _spec_divides(arr.shape, spec, mesh):
                    return jax.device_put(arr, NamedSharding(mesh, spec))
                logger.warning(
                    "%s: spec %s does not divide shape %s over mesh %s; "
                    "replicating on the slice", self.node_id, spec,
                    tuple(arr.shape), dict(mesh.shape))
            # replicate across the slice: compatible under jit with leaves
            # that DID reshard onto the slice's mesh
            return jax.device_put(arr, NamedSharding(mesh, PartitionSpec()))
        if isinstance(arr, jax.Array):   # a pinned-host buffer
            return jax.device_put(arr, arr.sharding.with_memory_kind("device"))
        return jnp.asarray(arr)

    # ----------------------------------------------------------- register
    @_locked
    def register(self, job_id: str, tree, tier: Tier = Tier.DEVICE,
                 prefix: str = "params") -> List[str]:
        """Adopt a pytree of tensors under canonical keys. Re-registering an
        existing key with the same version only bumps the refcount (§4.5.2
        dedup of data-parallel replicas)."""
        flat = common.canonical_flat(tree, is_leaf=lambda x: hasattr(x, "shape"))
        keys = []
        for sub, leaf in flat.items():
            key = f"{job_id}/{prefix}/{sub}"
            if key in self.entries:
                self.entries[key].refcount += 1
            else:
                self.entries[key] = Entry(
                    key=key, tier=tier, nbytes=_nbytes(leaf),
                    ref=leaf, last_touch=self.clock(),
                    spec=self._leaf_spec(leaf))
            keys.append(key)
        self._evict_if_needed()
        return keys

    @_locked
    def reregister(self, job_id: str, tree, tier: Tier = Tier.DEVICE,
                   prefix: str = "params") -> List[str]:
        """Replace the job's keys under ``prefix`` with ``tree`` in one step
        (unregister, then :meth:`register`): a concurrent :meth:`gather`
        sees the old tree or the new one, never a part of either."""
        self.unregister(self.keys_for(job_id, prefix))
        return self.register(job_id, tree, tier, prefix)

    @_locked
    def keys_for(self, job_id: str, prefix: Optional[str] = None) -> List[str]:
        pre = f"{job_id}/" + (f"{prefix}/" if prefix else "")
        return [k for k in self.entries if k.startswith(pre)]

    @_locked
    def unregister(self, keys: Sequence[str]):
        jobs = set()
        for k in keys:
            e = self.entries.get(k)
            if e is None:
                continue
            e.refcount -= 1
            if e.refcount <= 0:
                if e.path and os.path.exists(e.path):
                    os.unlink(e.path)
                del self.entries[k]
                jobs.add(k.split("/", 1)[0] + "/")
        for pre in jobs:
            if not any(k.startswith(pre) for k in list(self.entries)):
                for k in list(self._slots):
                    if k.startswith(pre):
                        self._slots.pop(k, None)

    def _take_slot(self, e: Entry):
        """The key's pinned buffer, if ``e``'s offload can land in it: same
        shape and dtype, and the same per-device layout (a step's outputs
        may name it by another spec than the load did)."""
        s, x = self._slots.pop(e.key, None), e.ref
        if (s is None or s.shape != x.shape or s.dtype != x.dtype
                or not s.sharding.is_equivalent_to(
                    x.sharding.with_memory_kind("pinned_host"), x.ndim)):
            return None
        return s

    # ------------------------------------------------------ tier movement
    @_locked
    def offload(self, keys: Sequence[str], to: Tier = Tier.HOST) -> float:
        """Move state down the hierarchy. Returns elapsed seconds.

        Every device-to-host copy of the call is issued before any is
        waited on (:func:`_to_host`), each into the pinned buffer its key
        was last loaded from where there is one, and the call returns once
        all have landed; only then are the device buffers dropped.

        Timed through the injected ``self.clock`` (NOT time.monotonic): under
        a VirtualClock replay transfers take zero virtual time, so measured
        C_setup feedback — and therefore HRRS admission — stays
        deterministic."""
        t0 = self.clock()
        moved = leaves = 0
        with self._move_span("switch.offload", keys, lambda e: e.tier < to):
            todo = [e for k in keys
                    if (e := self.entries.get(k)) is not None and e.tier < to]
            on_device = [e for e in todo if e.tier == Tier.DEVICE
                         and isinstance(e.ref, jax.Array)]
            landed = _to_host([e.ref for e in on_device],
                              [self._take_slot(e) for e in on_device])
            for e, arr in zip(on_device, landed):
                e.ref = arr
            for e in todo:
                # a key may vanish mid-offload when a deployment detaches
                # concurrently (teardown unregisters); skipping it is the move
                if self.entries.get(e.key) is not e:
                    continue
                if to == Tier.DISK:
                    os.makedirs(self.disk_dir, exist_ok=True)
                    path = os.path.join(self.disk_dir,
                                        e.key.replace("/", "__") + ".npy")
                    arr = np.asarray(e.ref)
                    e.is_bf16 = arr.dtype == jnp.bfloat16
                    np.save(path, arr.view(np.uint16) if e.is_bf16 else arr)
                    e.path = path
                    e.ref = None
                e.tier = to
                e.last_touch = self.clock()
                moved += e.nbytes
                leaves += 1
        dt = self.clock() - t0
        self._record("offload", moved, dt)
        if moved and self.telemetry is not None:
            # synchronous (every copy has landed), so the clock timed it
            self.telemetry.add("switch.offload_s", dt)
            self.telemetry.add("switch.offload_bytes", moved)
            self.telemetry.add("switch.offload_leaves", leaves)
        return dt

    @_locked
    def prefetch(self, keys: Sequence[str], shardings=None) -> float:
        """Move state up to DEVICE (scheduler-directed prefetch). Timed via
        ``self.clock`` for the same determinism contract as offload."""
        t0 = self.clock()
        moved = 0
        loaded = []
        with self._move_span("switch.load", keys,
                             lambda e: e.tier != Tier.DEVICE):
            for i, k in enumerate(keys):
                e = self.entries.get(k)
                if e is None or e.tier == Tier.DEVICE:
                    continue
                if e.tier == Tier.DISK:
                    arr = np.load(e.path)
                    if e.is_bf16:
                        arr = arr.view(jnp.bfloat16)
                    e.ref = arr
                arr = e.ref
                shd = None
                if shardings is not None:
                    shd = (shardings[i]
                           if isinstance(shardings, (list, tuple))
                           else shardings.get(k))
                if shd is not None:
                    e.ref = jax.device_put(arr, shd)
                    if isinstance(shd, NamedSharding):
                        e.spec = shd.spec
                else:
                    # no explicit target layout: restore the entry's recorded
                    # spec on THIS node's mesh slice (device-aware residency)
                    e.ref = self._to_device(arr, e.spec)
                    e.spec = self._leaf_spec(e.ref)
                if isinstance(arr, jax.Array):
                    self._slots[k] = arr      # the next offload lands here
                e.tier = Tier.DEVICE
                e.last_touch = self.clock()
                moved += e.nbytes
                loaded.append(e.ref)
        dt = self.clock() - t0
        self._record("load", moved, dt)
        tel = self.telemetry
        if moved and tel is not None:
            tel.add("switch.load_bytes", moved)
            tel.when_ready(loaded, lambda t: tel.add("switch.load_s", t - t0),
                           donated="switch.load_donated")
        self._evict_if_needed()
        return dt

    @_locked
    def _evict_if_needed(self):
        """Capacity-aware LRU eviction DEVICE->HOST->DISK."""
        while self._tier_bytes(Tier.DEVICE) > self.device_capacity:
            victims = [e for e in self.entries.values() if e.tier == Tier.DEVICE]
            victim = min(victims, key=lambda e: e.last_touch)
            self.offload([victim.key], Tier.HOST)
        while self._tier_bytes(Tier.HOST) > self.host_capacity:
            victims = [e for e in self.entries.values() if e.tier == Tier.HOST]
            victim = min(victims, key=lambda e: e.last_touch)
            self.offload([victim.key], Tier.DISK)

    # --------------------------------------------------------- estimates
    def load_time_estimate(self, nbytes: int) -> float:
        return self._estimate("load", nbytes, 1e10)

    def offload_time_estimate(self, nbytes: int) -> float:
        return self._estimate("offload", nbytes, 1e10)

    @_locked
    def job_bytes(self, job_id: str) -> int:
        return sum(e.nbytes for k, e in self.entries.items()
                   if k.startswith(f"{job_id}/"))

    # ------------------------------------------------------- gather trees
    @_locked
    def gather(self, job_id: str, template, prefix: str = "params"):
        """Rebuild a pytree from managed entries (any tier; loads lazily from
        disk, gives host tensors as numpy)."""
        flat = {}
        pre = f"{job_id}/{prefix}/"
        for k, e in self.entries.items():
            if not k.startswith(pre):
                continue
            if e.tier == Tier.DISK:
                arr = np.load(e.path)
                if e.is_bf16:
                    arr = arr.view(jnp.bfloat16)
            elif e.tier == Tier.HOST:
                arr = np.asarray(e.ref)
            else:
                arr = e.ref
            flat[k[len(pre):]] = arr
        return common.canonical_unflatten(
            template, flat, is_leaf=lambda x: hasattr(x, "shape"))

    # ------------------------------------------------ §4.5.3 materialise
    def materialize_checkpoint(self, job_id: str, template, path: str,
                               step: int = 0, prefix: str = "params") -> str:
        """Checkpoint = materialisation from managed state — works even if
        (part of) the state is offloaded; no user-triggered export path."""
        from repro.train import checkpoint as ckpt
        tree = self.gather(job_id, template, prefix)
        return ckpt.save(path, tree, step=step,
                         extra_meta={"job_id": job_id, "node": self.node_id})

    def sync_weights(self, job_id: str, template,
                     target_shardings=None, prefix: str = "params",
                     dtype=None):
        """Weight synchronisation to a rollout deployment: materialise
        training-visible state into the target layout. Zero-redundancy: with
        NamedShardings, jax.device_put moves only the slices each target
        shard needs."""
        tree = self.gather(job_id, template, prefix)
        if dtype is not None:
            tree = jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)
        if target_shardings is not None:
            tree = jax.tree.map(jax.device_put, tree, target_shardings)
        else:
            tree = jax.tree.map(jnp.asarray, tree)
        return tree

    def migrate(self, job_id: str, dst: "StateManager") -> int:
        """Cross-node deployment migration (§4.5.3): mirror managed state to
        the destination node's manager and drop it here.

        Cross-mesh resharding: a DEVICE-tier entry is gathered off THIS
        node's slice (device_get) and re-laid-out on the destination
        slice's mesh with its recorded PartitionSpec (device_put with the
        target NamedSharding); a destination without a mesh slice receives
        host-tier copies (the legacy path). Transactional: nothing is
        unregistered here until EVERY entry has landed on ``dst`` — a
        mid-copy failure rolls the destination back and leaves this node's
        state (all tiers, including disk files) untouched. Timed through
        the injected clock, so the realized reshard cost feeds the
        control plane's migration floor without breaking VirtualClock
        replay (virtual transfers take zero time and are discarded)."""
        t0 = self.clock()
        keys = list(self.keys_for(job_id))
        cross_mesh = (dst.mesh_slice is not None
                      and (self.mesh_slice is None
                           or dst.mesh_slice.devices != self.mesh_slice.devices))
        staged: List[str] = []
        moved = 0
        try:
            for k in keys:
                e = self.entries[k]
                if e.tier == Tier.DEVICE:
                    arr = np.asarray(jax.device_get(e.ref))
                elif e.tier == Tier.DISK:
                    arr = np.load(e.path)
                    if e.is_bf16:
                        arr = arr.view(jnp.bfloat16)
                else:
                    arr = np.asarray(e.ref)
                tier, ref, spec = Tier.HOST, arr, e.spec
                if e.tier == Tier.DEVICE and dst.mesh_slice is not None:
                    # reshard onto the target slice: the entry arrives
                    # device-resident in the layout its spec dictates there
                    ref = dst._to_device(arr, e.spec)
                    tier = Tier.DEVICE
                    spec = dst._leaf_spec(ref)
                dst.entries[k] = Entry(key=k, tier=tier, nbytes=e.nbytes,
                                       ref=ref, version=e.version,
                                       last_touch=dst.clock(), spec=spec)
                staged.append(k)
                moved += e.nbytes
        except Exception:
            for k in staged:     # rollback: the source still owns the state
                dst.entries.pop(k, None)
            raise
        for k in keys:
            self.unregister([k])
        dst._evict_if_needed()
        dt = self.clock() - t0
        self._record("migrate", moved, dt)
        self.last_migrate = {"bytes": moved, "seconds": dt,
                             "cross_mesh": cross_mesh, "keys": len(keys)}
        return moved

    # ------------------------------------------- §4.5.4 host optimizer
    def host_optimizer_step(self, job_id: str, grads_tree, template,
                            lr: float = 3e-5, b1: float = 0.9,
                            b2: float = 0.95, eps: float = 1e-8,
                            prefix: str = "params") -> int:
        """CPU AdamW over host-resident canonical state (ZeRO-offload): runs
        off the device critical path while other WPGs execute. Moments are
        created lazily on HOST at first use. Returns the new step count."""
        pre = f"{job_id}/{prefix}/"
        gflat = common.canonical_flat(
            grads_tree, is_leaf=lambda x: hasattr(x, "shape"))
        step_key = f"{job_id}/opt/step"
        if step_key not in self.entries:
            self.entries[step_key] = Entry(step_key, Tier.HOST, 8,
                                           ref=np.zeros((), np.int64))
        step = int(self.entries[step_key].ref) + 1
        self.entries[step_key].ref = np.asarray(step, np.int64)
        c1 = 1.0 - b1 ** step
        c2 = 1.0 - b2 ** step
        for sub, g in gflat.items():
            pkey = pre + sub
            e = self.entries[pkey]
            if e.tier == Tier.DEVICE:
                # pull a host copy; device copy becomes stale until sync
                e.ref = np.asarray(jax.device_get(e.ref))
                e.tier = Tier.HOST
            p = np.asarray(e.ref, np.float32)
            g32 = np.asarray(jax.device_get(g), np.float32)
            for mom, beta in (("mu", b1), ("nu", b2)):
                mkey = f"{job_id}/opt/{mom}/{sub}"
                if mkey not in self.entries:
                    self.entries[mkey] = Entry(mkey, Tier.HOST,
                                               g32.nbytes,
                                               ref=np.zeros_like(g32))
            mu = self.entries[f"{job_id}/opt/mu/{sub}"]
            nu = self.entries[f"{job_id}/opt/nu/{sub}"]
            mu.ref = b1 * mu.ref + (1 - b1) * g32
            nu.ref = b2 * nu.ref + (1 - b2) * np.square(g32)
            upd = (mu.ref / c1) / (np.sqrt(nu.ref / c2) + eps)
            newp = (p - lr * upd)
            e.ref = newp.astype(np.asarray(e.ref).dtype) \
                if np.asarray(e.ref).dtype != np.float32 else newp
            e.version += 1
        return step
