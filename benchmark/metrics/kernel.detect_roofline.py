"""The detect program's share of its roofline, in percent: the least time
the chip could take for the window's compulsory work (``work.py``: every
acquisition read once and the least result written once, against the
peak bandwidth; every acquisition scored once, against the peak rate;
the larger binds) over the detect program's device time in the trace."""

from benchmark import work


def read(ctx):
    k, peak = ctx.get("kernel_s"), ctx.get("peak")
    if not k or not peak:
        return None
    w = work.compulsory(ctx["pixels"], ctx["acquisitions"], ctx["bands"],
                        ctx["detection_bands"])
    least = work.least_time(w, peak)["seconds"] * ctx["chips"]
    return 100.0 * least / k
