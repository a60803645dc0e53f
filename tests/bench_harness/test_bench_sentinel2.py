"""The Sentinel-2 L2A cell driven end to end on the CPU at a tiny size, and
its per-layer readers on hand-built snapshots.

The run is the harness's own (``test_bench_harness.tiny``: 10x10 px
chips, two chips a batch), over three years of the cell's S2A+S2B
schedule, through ``run_chunk`` into sqlite and compared with the plain
reference at twelve bands.
"""

import json

import numpy as np
import pytest

from benchmark import control, harness
from test_bench_harness import (ROOT, SEED, knobs, over_limit,  # noqa: F401
                                run, tiny)

CELL = "sentinel2-l2a-conus.breaks"
METRICS = ("store.write_ns_per_value", "egress.format_ns_per_value",
           "kernel.device_us_per_px")


def s2_tiny():
    cs = tiny(harness.load_cell(ROOT, CELL))
    # tiny() takes 2000-2004, before either platform flew
    cs["config"]["acquired"] = "2019-01-01/2022-01-01"
    return cs


def test_sound_run_of_the_sentinel2_cell(knobs):
    r = run(knobs(s2_tiny()))
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["mismatch_px_pct"]["value"] == 0.0
    assert r["checks"]["missing_rows"]["value"] == 0.0
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"pixels_per_s", "setup_s"}


def _swapped_bands(monkeypatch):
    """The stored rows of two bands (coastal and red) change places."""
    from firebird_tpu.ccd import format as ccdformat

    orig = ccdformat.batch_frames

    def batch_frames(packed, seg, n_real=None):
        out = orig(packed, seg, n_real)
        for _, frames in out:
            s = frames["segment"]
            for suffix in ("mag", "rmse", "coef", "int"):
                s[f"ca{suffix}"], s[f"re{suffix}"] = \
                    s[f"re{suffix}"], s[f"ca{suffix}"]
        return out

    monkeypatch.setattr(ccdformat, "batch_frames", batch_frames)


def test_swapped_band_columns_are_not_correct(monkeypatch, knobs):
    cs = knobs(s2_tiny())
    _swapped_bands(monkeypatch)
    r = run(cs)
    assert r["correct"] is False
    assert "coef_gap" in over_limit(r)
    json.loads(json.dumps(r, allow_nan=False))    # a valid JSON line


@pytest.mark.parametrize("seed", [7, 2**32 + 11])
def test_control_fails_a_limit(seed, monkeypatch):
    """The reference in bfloat16 in the program's place fails the cell's
    limits at twelve bands and T=448 (chips cut to 30x30 px in this test)."""
    monkeypatch.setattr(harness, "SAMPLE_PX", 8)
    cs = harness.load_cell(ROOT, CELL)
    cs["config"]["sensor"]["chip_side"] = 30
    cs["config"]["pool_archives"] = 2
    lim = harness.limits(ROOT, CELL)
    nums = control.readings(cs, seed, seconds=1)
    assert [k for k in lim if nums[k] > lim[k]], (nums, lim)


def test_cell_declares_its_per_value_metrics():
    cs = harness.load_cell(ROOT, CELL)
    assert cs["cell"]["chips"] == 1
    assert cs["config"]["driver"]["chips_per_batch"] == 1
    assert [m["name"] for m in cs["per_layer"]] == list(METRICS)
    for m in cs["per_layer"]:
        assert (m["moves"], m["better"]) == ("pixels_per_s", "lower")
    assert harness.program_sensor(cs["config"]).store_prefixes == \
        tuple(cs["config"]["store_prefixes"])


@pytest.mark.parametrize("name, snap, ctx, value", [
    ("store.write_ns_per_value",
     {"histograms": {"store_write_seconds": {"count": 9, "sum": 3.0}},
      "counters": {"store_values_written": 1_500_000}}, {}, 2000.0),
    ("egress.format_ns_per_value",
     {"histograms": {"egress_format_seconds": {"count": 4, "sum": 0.6}},
      "counters": {"store_values_written": 2_000_000}}, {}, 300.0),
    ("kernel.device_us_per_px", {}, {"kernel_s": 9.0, "chips": 2,
                                     "pixels": 90_000}, 50.0),
])
def test_per_value_readers(name, snap, ctx, value):
    ctx = dict(dict(snapshot=snap, chips=2, pixels=90_000), **ctx)
    assert harness.read_metric(ROOT, name, ctx) == pytest.approx(value)


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("snap", [
    {}, {"histograms": {"store_write_seconds": {"count": 2, "sum": 1.0},
                        "egress_format_seconds": {"count": 2, "sum": 1.0}}},
    {"counters": {"store_values_written": 10}}])
def test_per_value_readers_read_none_without_their_sources(name, snap):
    """A program that counts no stored values (the parent of this cell)
    or a run with no device trace gives no reading, and no error."""
    assert harness.read_metric(ROOT, name, {"snapshot": snap, "chips": 2,
                                            "pixels": 90_000}) is None
