"""Host milliseconds per chip the drain thread waited to put frames on the
async writer's full queue: writer backpressure inside
egress.drain_ms_per_chip (store_queue_wait_seconds)."""

HISTOGRAM = "store_queue_wait_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
