"""Peak device memory in use, GB (memory_stats()["peak_bytes_in_use"] of
the first device, read after the window)."""


def read(ctx):
    v = (ctx.get("memory_stats") or {}).get("peak_bytes_in_use")
    return None if v is None else v / 1e9
