"""Host milliseconds per chip of the drain's own formatting: the int-coded
decode and format.batch_frames, without the queued writes
(egress_format_seconds)."""

HISTOGRAM = "egress_format_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
