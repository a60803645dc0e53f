"""Writer-thread CPU milliseconds per chip inside the sqlite backend's writes
(time.thread_time, store_write_cpu_seconds); store.write_ms_per_chip minus
this is the writer waiting on the GIL or the disk."""

HISTOGRAM = "store_write_cpu_seconds"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    if not h or not h.get("count"):
        return None
    return 1000.0 * h["sum"] / ctx["chips"]
