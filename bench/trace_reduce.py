"""From a profiler trace to device busy time, idle gaps and spans.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes (``/device:TPU:<n>``) carry one event per executed HLO
operation on their ``XLA Ops`` line, and per program on ``XLA Modules``;
host planes carry the benchmark's own ``TraceAnnotation`` spans, whose names
start with ``bench.``. All share the trace's clock, in nanoseconds.

Busy time is the union of a chip's operation intervals; idle is the rest
of the window. An idle gap is named by the benchmark span that covers most
of it (``dispatch`` where none does).
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# which span names a gap when two cover it equally
GAP_PRIORITY = ("verifier", "switch", "pack", "GENERATE", "UPDATE_ACTOR",
                "INIT")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def total(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` outside the merged ``busy`` list."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap: Interval, spans: Dict[str, List[Interval]]) -> str:
    best, best_len = "dispatch", 0.0
    for name in sorted(spans, key=lambda n: (GAP_PRIORITY.index(n)
                                             if n in GAP_PRIORITY else 99, n)):
        cover = overlap([gap], spans[name])
        if cover > best_len:
            best, best_len = name, cover
    return best


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``;
    ``jit_scan(123)`` -> ``jit_scan``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0] if "(" in name and " " not in name else name


def _named(ops, modules):
    """Each op named ``<program>/<op>`` by the program running when it
    started (both lists in time order)."""
    out, j = [], 0
    modules = sorted(modules)
    for s, e, name in sorted(ops):
        while j < len(modules) and modules[j][1] < s:
            j += 1
        mod = (short_name(modules[j][2]) + "/"
               if j < len(modules) and modules[j][0] <= s else "")
        out.append((s, e, mod + short_name(name)))
    return out


class Trace:
    """Device operations per chip and benchmark spans of one trace."""

    def __init__(self, ops: Dict[str, List[Tuple[float, float, str]]],
                 spans: Dict[str, List[Interval]]):
        self.ops = ops                  # chip plane -> [(start, end, name)]
        self.spans = spans              # span name -> [(start, end)]

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops: Dict[str, list] = {}
        spans: Dict[str, list] = collections.defaultdict(list)
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                lines = {line.name: line for line in plane.lines}
                line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
                if line is None:
                    continue
                mods = lines.get(MODULES_LINE)
                evs = _named([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events],
                             [] if mods is None or mods is line else
                             [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in mods.events])
                if evs:
                    ops[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans[e.name[len(SPAN_PREFIX):]].append(
                                (e.start_ns, e.start_ns + e.duration_ns))
        return cls(ops, dict(spans))

    def window(self) -> Optional[Interval]:
        w = self.spans.get("window")
        return (w[0][0], w[0][1]) if w else None

    def busy(self, lo: float, hi: float) -> Dict[str, List[Interval]]:
        return {chip: clip(union([(s, e) for s, e, _ in evs]), lo, hi)
                for chip, evs in self.ops.items()}

    def reduce(self) -> dict:
        """Everything the per-layer readers and the result line need, in
        seconds, over the ``window`` span."""
        win = self.window()
        if win is None:
            raise ValueError("trace has no bench.window span")
        lo, hi = win
        busy = self.busy(lo, hi)
        spans = {n: clip(union(v), lo, hi) for n, v in self.spans.items()
                 if n != "window"}
        chips = sorted(busy)
        op_time: Dict[str, float] = collections.Counter()
        for chip in chips:
            for s, e, name in self.ops[chip]:
                if e > lo and s < hi:
                    op_time[name] += (min(e, hi) - max(s, lo)) / len(chips)
        named = []
        for chip in chips:
            for g in gaps(busy[chip], lo, hi):
                named.append((name_gap(g, spans), (g[1] - g[0]) / len(chips)))
        named.sort(key=lambda x: -x[1])
        return {
            "window_s": (hi - lo) / 1e9,
            "chips": len(chips),
            "busy_s": sum(total(b) for b in busy.values()) / max(len(chips), 1)
            / 1e9,
            "busy": busy,
            "spans": spans,
            "breakdown": {
                "device_ops": [[n, t / 1e9] for n, t in
                               sorted(op_time.items(), key=lambda x: -x[1])[:10]],
                "idle_gaps": [[n, t / 1e9] for n, t in named[:10]],
            },
        }


def busy_in(reduced: dict, span: str) -> float:
    """Seconds of device busy time inside the benchmark span ``span``,
    averaged over chips."""
    busy = reduced["busy"]
    if not busy:
        return 0.0
    spans = reduced["spans"].get(span, [])
    return sum(overlap(b, spans) for b in busy.values()) / len(busy) / 1e9
