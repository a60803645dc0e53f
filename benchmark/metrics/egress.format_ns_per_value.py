"""Host nanoseconds of the drain's own formatting (the int-coded decode and
format.batch_frames, egress_format_seconds) per value the writer stored
(store_values_written), so sensors of different band counts compare."""

HISTOGRAM = "egress_format_seconds"
COUNTER = "store_values_written"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    n = ctx["snapshot"].get("counters", {}).get(COUNTER)
    if not h or not h.get("count") or not n:
        return None
    return 1e9 * h["sum"] / n
