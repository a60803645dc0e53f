"""Host milliseconds per chip in the sqlite backend's writes (the async
writer's store_write_seconds, all three tables)."""

HISTOGRAM = "store_write_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
