"""Host milliseconds per chip in ingest.pack (the program's
pipeline_pack_seconds span histogram, summed over the window's batches,
over the window's chips)."""

HISTOGRAM = "pipeline_pack_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
