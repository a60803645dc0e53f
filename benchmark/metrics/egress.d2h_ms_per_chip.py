"""Host milliseconds per chip the drain spent in its bulk device->host
fetch of the int-coded results, inside egress.drain_ms_per_chip
(pipeline_d2h_seconds)."""

HISTOGRAM = "pipeline_d2h_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
