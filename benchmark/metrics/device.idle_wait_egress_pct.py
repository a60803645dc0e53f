"""Percent of the traced window in which the device was idle while the
dispatching thread waited on egress: inside the program's
``firebird.wait_egress`` (a pipeline slot at pipeline_depth, or the
chunk's last drains) or ``firebird.store_flush`` (the chunk's flush)."""

from benchmark import spans

SPANS = ("firebird.wait_egress", "firebird.store_flush")


def read(ctx):
    return spans.idle_under_pct(spans.run_events(ctx), SPANS)
