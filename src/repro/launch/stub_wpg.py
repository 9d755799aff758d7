"""Spawnable stub WorkerProcessGroup for process-plane tests and benches.

Lives under ``src/`` (not ``benchmarks/`` or ``tests/``) because spawned
group processes must be able to import the factory by name — the pipe
carries ``"repro.launch.stub_wpg:make_busy_wpg"``, never a pickled
callable. ``needs_state_manager = False`` keeps the child jax-free (the
process plane gives it a ``_LiteSM``), so a stub group spawns fast.

Per-op kwargs drive behaviour:

- ``busy_s``   — burn CPU for that long (pure-Python loop, so a THREAD
  worker holds the GIL: this is what makes the thread-vs-process overlap
  comparison honest)
- ``sleep_s``  — blocking sleep (releases the GIL; models device-bound
  work in thread mode)
- ``crash``    — hard-exit the worker process mid-op (``os._exit``), the
  robustness-test stand-in for a device/process failure
- ``fail``     — raise inside ``execute`` (a remote op error, not a death)
- ``payload_mb`` / ``payload_kib`` — return that much numpy array in the
  result (cached per size across ops), so transport tests/benches drive
  real bytes through the reply path
- ``stored_sum`` — return the checksum of the last ``_store``\\ d params
  (verifies a cross-child weight sync actually landed)

``make_crash_store_wpg`` builds a group whose ``_store`` hard-exits — the
stand-in for a child dying mid-``sync_weights`` with shm descriptors in
flight. ``sync_mb`` / ``sync_kib`` in the spec overrides size
``host_params``.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict


class BusyWPG:
    """Minimal WPG protocol surface, compute-bound on demand."""

    needs_state_manager = False

    def __init__(self, spec, sm):
        self.spec = spec
        self.sm = sm
        self.exec_log: list = []
        self._resident = True

    @property
    def job_prefix(self) -> str:
        return f"{self.spec.job_id}:{self.spec.deployment_id}"

    def resident(self) -> bool:
        return self._resident

    def ensure_resident(self) -> float:
        self._resident = True
        return 0.0

    def offload(self, to=None) -> float:
        self._resident = False
        return 0.0

    # ------------------------------------------------ weight-sync surface
    def host_params(self) -> Dict[str, Any]:
        """Deterministic host-staged params sized by the spec's ``sync_mb``
        (MiB, default 1) or ``sync_kib`` override — what a cross-child sync
        exports. Cached: repeated syncs time the transport, not arange."""
        params = getattr(self, "_host_params", None)
        if params is None:
            import numpy as np
            ov = dict(self.spec.overrides or ())
            kib = (int(ov["sync_kib"]) if "sync_kib" in ov
                   else int(ov.get("sync_mb", 1)) << 10)
            n = (kib << 10) // 4
            params = self._host_params = {"w": np.arange(n, dtype=np.float32)}
        return params

    def _store(self, params=None) -> None:
        self.stored = params

    def execute(self, qop) -> Dict[str, Any]:
        t0 = time.monotonic()
        kw = qop.kwargs
        if kw.get("crash"):
            os._exit(43)
        if kw.get("fail"):
            raise RuntimeError(f"stub op {qop.req_id} asked to fail")
        busy = float(kw.get("busy_s", 0.0))
        if busy > 0.0:
            # pure-Python spin against THREAD CPU time, not wall clock: a
            # GIL-starved thread must take proportionally longer wall time
            # (a wall deadline would let contended threads "finish" on
            # schedule having done less work, faking overlap)
            deadline = time.thread_time() + busy
            x = 0
            while time.thread_time() < deadline:
                x += 1
        sleep = float(kw.get("sleep_s", 0.0))
        if sleep > 0.0:
            time.sleep(sleep)
        t1 = time.monotonic()
        # t0/t1 on the host-wide monotonic clock: intervals from different
        # worker processes are comparable
        out = {"op": qop.op.value, "req_id": qop.req_id, "pid": os.getpid(),
               "seconds": t1 - t0, "t0": t0, "t1": t1}
        kib = int(kw.get("payload_kib", 0)) + (int(kw.get("payload_mb", 0))
                                               << 10)
        if kib > 0:
            import numpy as np
            # cached per size so repeated ops time the TRANSPORT, not the
            # array construction (transport_bench reps hit this path)
            cache = getattr(self, "_payload_cache", None)
            if cache is None:
                cache = self._payload_cache = {}
            arr = cache.get(kib)
            if arr is None:
                arr = cache[kib] = np.arange((kib << 10) // 8,
                                             dtype=np.float64)
            out["data"] = arr
        if kw.get("stored_sum"):
            import numpy as np
            stored = getattr(self, "stored", None) or {}
            out["stored_sum"] = float(sum(
                np.asarray(v, np.float64).sum() for v in stored.values()))
        return out


class CrashStoreWPG(BusyWPG):
    """Dies inside ``_store`` — a target child crashing mid-sync while the
    source child's shm descriptors are in flight."""

    def _store(self, params=None) -> None:
        os._exit(44)


def make_busy_wpg(spec, sm) -> BusyWPG:
    return BusyWPG(spec, sm)


make_busy_wpg.needs_state_manager = False


def make_crash_store_wpg(spec, sm) -> CrashStoreWPG:
    return CrashStoreWPG(spec, sm)


make_crash_store_wpg.needs_state_manager = False
