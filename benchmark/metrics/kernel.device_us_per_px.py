"""Device microseconds per pixel of the detect program: the summed device
time of its runs in the traced window (the profiler's XLA Modules line)
over the pixels of the chips dispatched inside that window, so chips of
different sizes compare."""


def read(ctx):
    k = ctx.get("kernel_s")
    if not k:
        return None
    return 1e6 * k / (ctx["chips"] * ctx["pixels"])
