"""Host milliseconds per chip draining results: capacity probe, int-coded
d2h, decode, format.batch_frames and queueing the writes
(pipeline_drain_seconds)."""

HISTOGRAM = "pipeline_drain_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
