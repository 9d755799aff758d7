"""The served path's own spans and counters.

- :func:`span` marks a piece of work on the host as ``plexrl.<name>`` in the
  profiler's trace, on the same clock as the device's operations, so an idle
  gap of the chip can be put down to what the host was doing. A span carries
  the ids that exist where it opens: ``job``, ``req`` (the operation's
  ``req_id``) and ``cause`` (the ``req_id``\\ s it waited on, space
  separated). With no profiler running, a span costs a flag check.
- :class:`Counters` holds named float totals, always on. One belongs to each
  ``Router`` (``router.telemetry``); its TaskExecutor, StateManagers and
  client controllers add to it. A reader takes two :meth:`Counters.snapshot`
  s and subtracts them.
- :meth:`Counters.when_ready` times work to when its arrays are ready on the
  device, not to when it was enqueued, on one watcher thread per
  ``Counters``; the caller's thread is never held.

JAX's compile path is the one process-wide source: its listeners
(``jax.monitoring``) are registered once per process and every snapshot
merges their totals in, as ``jax.trace_s`` (tracing to a jaxpr),
``jax.lower_s`` (jaxpr to an MLIR module), ``jax.compile_s`` (the backend
compile, or the persistent-cache fetch that stands in for it),
``jax.cache_fetch_s`` (that fetch, where it served the program) and
``jax.compiles`` (backend compile events, fetches included).
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Callable, Dict, Optional

import jax

logger = logging.getLogger(__name__)

def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A trace span ``plexrl.<name>`` with the given ids as its stats."""
    return jax.profiler.TraceAnnotation("plexrl." + name, **ids)


# ------------------------------------------------------- JAX's compile path
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower_s",
    "/jax/core/compile/backend_compile_duration": "jax.compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_fetch_s",
}
_compile_lock = threading.Lock()
_compile_totals: Dict[str, float] = {}
_listening = False


def _on_duration(event: str, secs: float, **_):
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    with _compile_lock:
        _compile_totals[name] = _compile_totals.get(name, 0.0) + secs
        if name == "jax.compile_s":
            _compile_totals["jax.compiles"] = \
                _compile_totals.get("jax.compiles", 0.0) + 1.0


def _listen_to_compiles():
    """Register the compile-path listener, once per process (JAX keeps its
    listeners for the life of the process)."""
    global _listening
    with _compile_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


# ---------------------------------------------------------------- counters
class Counters:
    """Named float totals behind one lock, and a watcher that times work to
    ready. ``now`` is the owner's clock (a VirtualClock under replay)."""

    def __init__(self, now: Callable[[], float] = time.monotonic):
        self.now = now
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {}
        self._queue: Optional[queue.SimpleQueue] = None
        _listen_to_compiles()

    def add(self, name: str, value: float = 1.0):
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + value

    def snapshot(self) -> Dict[str, float]:
        """Every total so far, this owner's and the process's compile path."""
        with self._lock:
            out = dict(self._totals)
        with _compile_lock:
            out.update(_compile_totals)
        return out

    def when_ready(self, tree, fn: Callable[[float], None],
                   donated: Optional[str] = None):
        """Call ``fn(t_ready)`` once every array of ``tree`` is ready, from
        the watcher thread; at once, here, if ``tree`` holds none. An array
        found deleted (donated to a later op) counts as ready when found,
        and adds 1 to the counter ``donated`` if one is named."""
        # what jax.block_until_ready waits on: any leaf that can block
        arrays = [x for x in jax.tree.leaves(tree)
                  if hasattr(x, "block_until_ready")]
        if not arrays:
            fn(self.now())
            return
        with self._lock:
            if self._queue is None:
                self._queue = queue.SimpleQueue()
                threading.Thread(target=self._watch, args=(self._queue,),
                                 name="plexrl-ready", daemon=True).start()
            self._queue.put((arrays, fn, donated))

    def _watch(self, q: queue.SimpleQueue):
        while True:
            arrays, fn, donated = q.get()
            found_deleted = False
            for x in arrays:
                try:
                    x.block_until_ready()
                except RuntimeError:    # deleted: a later op donated it
                    found_deleted = found_deleted or x.is_deleted()
            t_ready = self.now()
            if found_deleted and donated is not None:
                self.add(donated)
            try:
                fn(t_ready)
            except Exception:  # noqa: BLE001 - the watcher must keep running
                logger.exception("ready callback raised")
            # drop the arrays before waiting: the next item may be long in
            # coming, and a held array keeps its device buffer alive
            del arrays, fn
