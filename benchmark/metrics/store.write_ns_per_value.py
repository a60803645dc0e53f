"""Host nanoseconds of the store's writes per stored value: the async
writer's store_write_seconds over store_values_written (rows times columns
of every frame it stored), so sensors of different band counts compare."""

HISTOGRAM = "store_write_seconds"
COUNTER = "store_values_written"


def read(ctx):
    h = ctx["snapshot"].get("histograms", {}).get(HISTOGRAM)
    n = ctx["snapshot"].get("counters", {}).get(COUNTER)
    if not h or not h.get("count") or not n:
        return None
    return 1e9 * h["sum"] / n
