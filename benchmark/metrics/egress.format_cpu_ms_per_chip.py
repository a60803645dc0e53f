"""Drain-thread CPU milliseconds per chip over the same formatting span
(time.thread_time, egress_format_cpu_seconds); egress.format_ms_per_chip
minus this is the time the drain waited inside it (the GIL or other)."""

HISTOGRAM = "egress_format_cpu_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
