"""Percent of the traced window in which the device was idle while the
dispatching thread waited on its next input batch: inside the program's
``firebird.wait_input`` (fetch, pack or h2d of the prefetch not done)."""

from benchmark import spans

SPANS = ("firebird.wait_input",)


def read(ctx):
    return spans.idle_under_pct(spans.run_events(ctx), SPANS)
