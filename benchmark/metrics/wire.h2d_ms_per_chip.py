"""Host milliseconds per chip staging inputs to the device (the program's
pipeline_stage_seconds, which blocks until the transfer lands)."""

HISTOGRAM = "pipeline_stage_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
