"""The per-layer readers of the program's egress spans and histograms, on
hand-built traces and registry snapshots."""

import os

import pytest

from benchmark import harness
from benchmark import spans
from benchmark import trace as tr

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6  # ns


def ev(plane, line, name, start_ms, dur_ms):
    return tr.Event(plane, line, name, start_ms * MS, dur_ms * MS)


# A 100 ms window: the device busy 10-40 and 70-80 ms (60% idle); the
# dispatch thread waits on input 0-10, on egress 30-60 (wait_egress) and
# 75-110 (store_flush, past the window's end).  Idle under the egress
# waits: 40-60 and 80-100 = 40 ms; under the input wait: 0-10 = 10 ms.
EVENTS = [
    ev(HOST, "python3", "bench.window", 0, 100),
    ev(HOST, "python3", "firebird.wait_input", 0, 10),
    ev(DEV, tr.OPS_LINE, "fusion.1", 10, 30),
    ev(DEV, tr.OPS_LINE, "fusion.2", 15, 10),            # nested
    ev(DEV, tr.MODULES_LINE, "jit__detect_batch_wire(1)", 10, 30),
    ev(HOST, "python3", "firebird.wait_egress", 30, 30),
    ev(HOST, "python3", "firebird.dispatch", 40, 1),     # not a wait
    ev(DEV, tr.OPS_LINE, "fusion.3", 70, 10),
    ev(HOST, "python3", "firebird.store_flush", 75, 35),
    ev(HOST, "drain", "firebird.format", 80, 20),        # another thread
]
WAITS = {"device.idle_wait_egress_pct": 40.0,
         "device.idle_wait_input_pct": 10.0}


@pytest.mark.parametrize("name", sorted(WAITS))
def test_idle_under_the_dispatch_waits(name):
    ctx = {"trace": tr.reduce(EVENTS), "events": EVENTS}
    v = harness.read_metric(ROOT, name, ctx)
    assert v == pytest.approx(WAITS[name])
    idle = harness.read_metric(ROOT, "device.idle_pct", ctx)
    assert idle == pytest.approx(60.0)


def test_the_waits_never_exceed_the_idle_share():
    ctx = {"trace": tr.reduce(EVENTS), "events": EVENTS}
    total = sum(harness.read_metric(ROOT, n, ctx) for n in WAITS)
    assert total <= harness.read_metric(ROOT, "device.idle_pct", ctx)


def test_idle_under_spans_averages_over_device_planes():
    # a second device busy through the egress wait: half the idle there
    ev2 = EVENTS + [ev("/device:TPU:1", tr.OPS_LINE, "fusion.9", 0, 100)]
    assert spans.idle_under_pct(ev2, ("firebird.wait_egress",
                                      "firebird.store_flush")) \
        == pytest.approx(20.0)


@pytest.mark.parametrize("name", sorted(WAITS))
@pytest.mark.parametrize("ctx", [
    {},                                                   # untraced run
    {"trace": {"devices": 1}, "events": []},              # empty trace
    {"trace": {"devices": 1},                              # a program that
     "events": [e for e in EVENTS                          # emits no
                if not e.name.startswith("firebird.")]},   # firebird.* span
    {"trace": {"devices": 1},                              # no device plane
     "events": [e for e in EVENTS if e.plane == HOST]},
], ids=["untraced", "empty", "no-spans", "no-device"])
def test_idle_under_spans_reads_none_without_a_trace(name, ctx):
    assert harness.read_metric(ROOT, name, ctx) is None


def test_events_found_in_the_harness_frame():
    """Where the context does not carry the events, the reader finds the
    list the harness's ``_run`` holds while it reads the metrics."""
    def _run():
        events = EVENTS
        assert events
        return harness.read_metric(ROOT, "device.idle_wait_egress_pct",
                                   {"trace": tr.reduce(EVENTS)})

    assert _run() == pytest.approx(40.0)


HISTOGRAMS = {
    "egress.wait_device_ms_per_chip": "egress_wait_device_seconds",
    "egress.format_ms_per_chip": "egress_format_seconds",
    "egress.format_cpu_ms_per_chip": "egress_format_cpu_seconds",
    "egress.queue_wait_ms_per_chip": "store_queue_wait_seconds",
    "store.write_cpu_ms_per_chip": "store_write_cpu_seconds",
}


@pytest.mark.parametrize("name", sorted(HISTOGRAMS))
def test_histogram_readers_per_chip(name):
    snap = {"histograms": {HISTOGRAMS[name]: {"count": 3, "sum": 1.5}}}
    assert harness.read_metric(ROOT, name, {"snapshot": snap, "chips": 20}) \
        == pytest.approx(75.0)
    # a program without the histogram (or with it empty) reads None
    for hists in ({}, {HISTOGRAMS[name]: {"count": 0}}):
        assert harness.read_metric(
            ROOT, name, {"snapshot": {"histograms": hists}, "chips": 20}) \
            is None


def test_new_metrics_declared_for_both_cells():
    cell = harness.load_cell(ROOT, "landsat-ard-conus.breaks")
    names = {m["name"] for m in cell["per_layer"]}
    assert set(HISTOGRAMS) | set(WAITS) <= names
