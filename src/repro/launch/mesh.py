"""Device plane: mesh construction and per-group mesh slices.

Functions (never module-level constants) so importing this module never
touches jax device state. Single pod: 256 chips (16x16, TPU v5e pod).
Multi-pod: 2 pods = 512 chips with a leading ``pod`` axis for cross-pod
data parallelism (DCN-connected in production; the dry-run proves the pod
axis shards).

The :class:`DevicePlane` carves ``jax.devices()`` into disjoint
:class:`MeshSlice`\\ s so that each node group owns real hardware affinity:
a group's WPGs build their jitted primitives against the group's mesh, its
StateManager records per-entry shardings on that mesh, and cross-group
migration means resharding (device_get on the source slice, device_put with
the target slice's NamedShardings). On CI the same code paths run against
virtual CPU devices via::

    XLA_FLAGS=--xla_force_host_platform_device_count=8

(set BEFORE jax's backend initialises — see launch/dryrun.py for the
env-before-import precedent).

Everything here is deterministic and clock-free: slice boundaries depend
only on the device list and the carve parameters, and acquisition follows
group-creation order — so the ``VirtualClock`` bit-identical-replay
contract is untouched.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

XLA_HINT = ("set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before importing jax to get N virtual CPU devices")


# device kind of the v5e pod (16x16 chips per pod) the production mesh
# describes; the dry-run and roofline read its peaks through hw_peaks
PRODUCTION_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"make_local_mesh(data={data}, model={model}) needs "
            f"{data * model} devices but only {n} are available; {XLA_HINT}")
    return jax.make_mesh((data, model), ("data", "model"))


def _slice_mesh(devices: Sequence) -> Mesh:
    """A (1, n) data×model mesh over an explicit device subset. Built from
    the raw device array (not jax.make_mesh) so the slice binds exactly the
    devices it was carved with."""
    arr = np.empty((1, len(devices)), dtype=object)
    for i, d in enumerate(devices):
        arr[0, i] = d
    return Mesh(arr, ("data", "model"))


@dataclasses.dataclass(frozen=True)
class MeshSlice:
    """A disjoint subset of the cluster's devices with its own mesh."""
    index: int
    devices: Tuple
    mesh: Mesh

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def device_ids(self) -> Tuple[int, ...]:
        return tuple(d.id for d in self.devices)


class DevicePlane:
    """Carves ``jax.devices()`` into disjoint mesh slices and leases them
    to node groups.

    ``carve(n_groups)`` partitions the device list into contiguous slices
    (``slice_size`` devices each when given, else ``len(devices) //
    n_groups``, minimum 1). ``slice_for_group(gid)`` leases the
    lowest-index free slice to a group; when every slice is held, groups
    share the least-loaded slice (deterministic tie-break by index) — on a
    single default device all groups share the lone one-device slice, which
    is exactly the pre-device-plane behaviour. ``release(gid)`` returns the
    lease on group retirement. Idempotent per group id, and thread-safe
    (the router acquires under its executor lock but benches drive a plane
    directly)."""

    def __init__(self, devices: Optional[Sequence] = None,
                 slice_size: Optional[int] = None):
        self._devices = tuple(devices) if devices is not None else None
        self.slice_size = slice_size
        self._slices: Optional[List[MeshSlice]] = None
        self._owner: Dict[int, int] = {}      # group id -> slice index
        self._holders: Dict[int, int] = {}    # slice index -> lease count
        self._lock = threading.Lock()

    # --------------------------------------------------------- device view
    def devices(self) -> Tuple:
        if self._devices is None:
            self._devices = tuple(jax.devices())
        return self._devices

    # -------------------------------------------------------------- carve
    def carve(self, n_groups: Optional[int] = None) -> List[MeshSlice]:
        """Partition the device list into disjoint slices. Callable once,
        before any lease; ``slices()`` carves lazily with defaults."""
        with self._lock:
            if self._owner:
                raise RuntimeError("cannot re-carve: slices are leased")
            return list(self._carve_locked(n_groups))

    def _carve_locked(self, n_groups: Optional[int] = None) -> List[MeshSlice]:
        devs = self.devices()
        if self.slice_size is not None:
            size = max(1, min(self.slice_size, len(devs)))
        elif n_groups:
            size = max(1, len(devs) // n_groups)
        else:
            size = 1
        n = max(1, len(devs) // size)
        self._slices = [
            MeshSlice(index=i, devices=tuple(devs[i * size:(i + 1) * size]),
                      mesh=_slice_mesh(devs[i * size:(i + 1) * size]))
            for i in range(n)]
        return self._slices

    def slices(self) -> List[MeshSlice]:
        with self._lock:
            if self._slices is None:
                self._carve_locked()
            return list(self._slices)

    # -------------------------------------------------------------- leases
    def slice_for_group(self, group_id: int) -> MeshSlice:
        """The slice leased to ``group_id`` (leasing one if needed)."""
        return self.acquire(group_id)

    def acquire(self, group_id: int) -> MeshSlice:
        with self._lock:
            if self._slices is None:
                self._carve_locked()
            idx = self._owner.get(group_id)
            if idx is None:
                free = [s.index for s in self._slices
                        if self._holders.get(s.index, 0) == 0]
                if free:
                    idx = free[0]
                else:  # oversubscribed: share the least-loaded slice
                    idx = min(self._slices,
                              key=lambda s: (self._holders.get(s.index, 0),
                                             s.index)).index
                self._owner[group_id] = idx
                self._holders[idx] = self._holders.get(idx, 0) + 1
            return self._slices[idx]

    def release(self, group_id: int):
        with self._lock:
            idx = self._owner.pop(group_id, None)
            if idx is not None:
                self._holders[idx] = max(0, self._holders.get(idx, 1) - 1)

    def slice_index(self, group_id: int) -> Optional[int]:
        with self._lock:
            return self._owner.get(group_id)

    def domains(self) -> Dict[int, int]:
        """group id -> slice index for every leased group (the placement
        layer's mesh-domain map: moves across domains pay the reshard)."""
        with self._lock:
            return dict(self._owner)


# Per-chip peaks keyed by ``jax.Device.device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s inter-chip interconnect per chip, taken here as 50 GB/s per
# link and direction).
HW_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "hbm_bw": 819e9,
        "ici_bw": 50e9,
        "hbm_bytes": 16e9,
    },
}


def hw_peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; a kind not in the table is
    an error, never a default."""
    try:
        return HW_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(HW_PEAKS)}") from None
