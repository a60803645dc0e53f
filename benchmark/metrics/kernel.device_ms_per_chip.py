"""Device milliseconds per chip of the detect program: the summed device
time of its runs in the traced window (the profiler's XLA Modules line)
over the chips dispatched inside that window."""


def read(ctx):
    k = ctx.get("kernel_s")
    if not k:
        return None
    return 1000.0 * k / ctx["chips"]
