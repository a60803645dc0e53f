"""Host milliseconds per chip the drain thread waited in the capacity probe
(reading n_segments blocks until the batch's kernel has finished): the
kernel wait inside egress.drain_ms_per_chip (egress_wait_device_seconds)."""

HISTOGRAM = "egress_wait_device_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
