"""The reader of the share of stored rows that took the sqlite store's
native bulk insert, on hand-built registry snapshots."""

import os

import pytest

from benchmark import harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
NAME = "store.native_row_share"


@pytest.mark.parametrize("native,written,pct", [
    (80_136, 80_160, 100.0 * 80_136 / 80_160),
    (0, 24, 0.0),
    (10, 10, 100.0),
])
def test_native_share_reader(native, written, pct):
    snap = {"counters": {"store_rows_native": native,
                         "store_rows_written": written}}
    assert harness.read_metric(ROOT, NAME, {"snapshot": snap,
                                            "chips": 8}) == pytest.approx(pct)


@pytest.mark.parametrize("counters", [
    {}, {"store_rows_written": 100}, {"store_rows_native": 0},
    {"store_rows_native": 0, "store_rows_written": 0}])
def test_native_share_reads_none_without_its_counters(counters):
    """The parent program counts no native rows: no reading, no error."""
    assert harness.read_metric(
        ROOT, NAME, {"snapshot": {"counters": counters}, "chips": 8}) is None


@pytest.mark.parametrize("cell", ["landsat-ard-conus.breaks",
                                  "landsat-ard-conus.coastal"])
def test_native_share_declared_for_the_landsat_cells(cell):
    per_layer = {m["name"]: m for m in harness.load_cell(ROOT, cell)[
        "per_layer"]}
    m = per_layer[NAME]
    assert (m["layer"], m["moves"], m["source"], m["better"]) == \
        ("store", "pixels_per_s", "program_counter", "higher")
