"""The control of ``correct``, at a size a test run holds: the reference in
bfloat16, put where the program's stored rows go, must fail the limits of
every cell on every seed (a sound float32 run passing them is
``test_bench_harness``'s)."""

import os

import pytest

from benchmark import control, harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
CELLS = ["landsat-ard-conus.breaks", "landsat-ard-conus.coastal"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [7, 2**32 + 11, 2**31 + 5])
def test_control_fails_a_limit(cell, seed, monkeypatch):
    monkeypatch.setattr(harness, "SAMPLE_PX", 6)
    cs = harness.load_cell(ROOT, cell)
    cs["config"]["driver"]["chips_per_batch"] = 1
    cs["config"]["pool_archives"] = 2
    lim = harness.limits(ROOT, cell)
    nums = control.readings(cs, seed, seconds=1)
    assert [k for k in lim if nums[k] > lim[k]], (nums, lim)

