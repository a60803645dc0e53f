"""Device idle time under the program's own host spans.

The program puts its stages on the profiler's clock as ``firebird.<span>``
host events (``firebird_tpu/obs/tracing.py``).  :func:`idle_under_pct` is
the share of the ``bench.window`` in which the device was idle while the
thread that owns the given spans sat inside one of them: busy time is the
union of each device plane's operations, clipped to the window, as
:func:`benchmark.trace.reduce` counts it; idle is its complement; the
spans' union is intersected with that idle time and averaged over the
device planes.  A trace with none of the spans (a program that does not
emit them) reads None.
"""

from __future__ import annotations

import sys

from benchmark import trace as tracelib


def run_events(ctx: dict):
    """The traced run's events: ``ctx["events"]`` where the harness passes
    them, else the list its ``_run`` holds while the readers run (the
    harness keeps the events in that frame and passes only their
    reduction).  None outside a traced run."""
    if "events" in ctx:
        return ctx["events"]
    if not ctx.get("trace"):
        return None
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "_run" and isinstance(
                f.f_locals.get("events"), list):
            return f.f_locals["events"]
        f = f.f_back
    return None


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under_pct(events, names) -> float | None:
    """Percent of the ``bench.window`` in which the device was idle inside
    any host span named in ``names``; None without the window, a device
    plane or any such span."""
    if not events:
        return None
    win = [e for e in events if e.name == tracelib.BENCH_PREFIX + "window"]
    planes = tracelib.device_planes(events)
    spans = [(e.start_ns, e.end_ns) for e in events
             if e.name in names and not e.plane.startswith("/device:")]
    if not win or not planes or not spans:
        return None
    lo, hi = win[0].start_ns, win[0].end_ns
    if hi <= lo:
        return None
    cover = _clip(tracelib.union(spans), lo, hi)
    covered = sum(e - s for s, e in cover)
    idle = 0.0
    for p in planes:
        on = [e for e in events if e.plane == p]
        ops = [e for e in on if e.line == tracelib.OPS_LINE] or on
        busy = _clip(tracelib.union((e.start_ns, e.end_ns) for e in ops),
                     lo, hi)
        idle += covered - _overlap(cover, busy)
    return 100.0 * idle / len(planes) / (hi - lo)
