"""The reader of the drain's bulk device->host fetch time, on hand-built
registry snapshots."""

import os

import pytest

from benchmark import harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
NAME = "egress.d2h_ms_per_chip"


@pytest.mark.parametrize("count,total,chips,ms", [
    (3, 1.5, 20, 75.0),
    (24, 0.0, 24, 0.0),
    (1, 8.6, 8, 1075.0),
])
def test_d2h_reader_per_chip(count, total, chips, ms):
    snap = {"histograms": {"pipeline_d2h_seconds":
                           {"count": count, "sum": total}}}
    assert harness.read_metric(ROOT, NAME, {"snapshot": snap,
                                            "chips": chips}) \
        == pytest.approx(ms)


@pytest.mark.parametrize("hists", [{}, {"pipeline_d2h_seconds": {"count": 0}},
                                   {"pipeline_drain_seconds":
                                    {"count": 2, "sum": 1.0}}])
def test_d2h_reader_reads_none_without_the_histogram(hists):
    assert harness.read_metric(
        ROOT, NAME, {"snapshot": {"histograms": hists}, "chips": 8}) is None


@pytest.mark.parametrize("cell", ["landsat-ard-conus.breaks",
                                  "landsat-ard-conus.coastal"])
def test_d2h_declared_for_both_cells(cell):
    per_layer = {m["name"]: m for m in harness.load_cell(ROOT, cell)[
        "per_layer"]}
    m = per_layer[NAME]
    assert (m["layer"], m["moves"], m["better"], m["unit"]) == \
        ("egress", "pixels_per_s", "lower", "ms")
