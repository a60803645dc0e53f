"""Percent of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""


def read(ctx):
    red = ctx.get("trace")
    if not red or not red.get("devices") or not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
