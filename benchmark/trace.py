"""Reduction of a profiler trace to device busy time, idle gaps, top
operations and per-program time.

The trace is flattened to :class:`Event` tuples first (``load``), so the
reduction itself (``reduce``) runs on plain data and is tested on small
hand-built traces.  Device planes are the ``/device:...`` planes; on each,
the ``XLA Ops`` line gives the operations (every line of the plane when it
has none) and the ``XLA Modules`` line the compiled programs.  Busy time is
the union of the operation intervals, so nested or overlapping events count
once.  Idle gaps are the stretches of the window that no operation covers;
each of 0.1 ms or more is named after the benchmark's own host span
(``bench.*``) that overlaps it most.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_PREFIX = "bench."
MIN_GAP_NS = 1e5     # gaps shorter than 0.1 ms are counted idle, not named


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals into disjoint ones, in order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_name(name: str) -> str:
    """An HLO op's name without its shape text (``%while.5 = (...)``)."""
    return name.split(" = ")[0].strip()


def self_times(op_events, lo, hi):
    """(name, seconds) of each op inside [lo, hi), less the time of the ops
    nested inside it, so a loop and its body are not counted twice."""
    evs = sorted(((max(e.start_ns, lo), min(e.end_ns, hi), op_name(e.name))
                  for e in op_events if min(e.end_ns, hi) > max(e.start_ns,
                                                                  lo)),
                 key=lambda x: (x[0], -x[1]))
    out, stack = [], []            # stack: [start, end, name, child_ns]
    for s, e, name in evs:
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[2], (top[1] - top[0] - top[3]) / 1e9))
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, name, 0.0])
    out += [(t[2], (t[1] - t[0] - t[3]) / 1e9) for t in stack]
    return out


def device_planes(events) -> list[str]:
    return sorted({e.plane for e in events if e.plane.startswith("/device:")})


def reduce(events: list[Event], window: tuple[float, float] | None = None,
           top: int = 10) -> dict:
    """``window`` is (start_ns, end_ns) on the trace clock; by default the
    benchmark's ``bench.window`` host span.  Returns the window length,
    busy seconds averaged over the device planes, the busy seconds of each
    device, the idle seconds after the last operation (``tail_idle_s``,
    averaged over the planes), the ``top`` operations by self time and the longest idle gaps,
    and the summed seconds of each compiled program (``modules``)."""
    if window is None:
        spans = [e for e in events if e.name == BENCH_PREFIX + "window"]
        if not spans:
            raise ValueError("no bench.window span in the trace")
        window = (spans[0].start_ns, spans[0].end_ns)
    lo, hi = window
    planes = device_planes(events)
    if not planes:
        return {"window_s": (hi - lo) / 1e9, "devices": 0}
    busy_per_dev = {}
    tail = 0.0
    ops = collections.Counter()
    modules = collections.Counter()
    gaps_all = []
    for p in planes:
        on = [e for e in events if e.plane == p]
        op_events = [e for e in on if e.line == OPS_LINE] or on
        busy = _clip(union((e.start_ns, e.end_ns) for e in op_events), lo, hi)
        busy_per_dev[p] = sum(e - s for s, e in busy) / 1e9
        tail += (hi - (busy[-1][1] if busy else lo)) / 1e9
        for name, sec in self_times(op_events, lo, hi):
            ops[name] += sec
        for e in on:
            if e.line == MODULES_LINE:
                d = min(e.end_ns, hi) - max(e.start_ns, lo)
                if d > 0:
                    modules[e.name] += d / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    host = [e for e in events if e.name.startswith(BENCH_PREFIX)
            and e.name != BENCH_PREFIX + "window"
            and not e.plane.startswith("/device:")]
    named = []
    by_span = collections.Counter()
    for s, e in gaps_all:
        if e - s < MIN_GAP_NS:
            continue
        best, cover = BENCH_PREFIX + "window", 0.0
        for h in host:
            c = min(e, h.end_ns) - max(s, h.start_ns)
            if c > cover:
                best, cover = h.name, c
        named.append((best, (e - s) / 1e9))
        by_span[best] += (e - s) / 1e9
    named.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": len(planes),
        "busy_s": sum(busy_per_dev.values()) / len(planes),
        "busy_per_device_s": busy_per_dev,
        "tail_idle_s": tail / len(planes),
        "device_ops": ops.most_common(top),
        "idle_gaps": named[:top],
        "idle_by_span_s": dict(by_span),
        "modules": dict(modules),
    }


def module_seconds(reduced: dict, pattern: str) -> float | None:
    """Summed device seconds of the programs whose name holds
    ``pattern``, or None where no such program ran in the window."""
    hits = [v for k, v in reduced.get("modules", {}).items() if pattern in k]
    return sum(hits) if hits else None
