"""The benchmark harness driven end to end on the CPU at a tiny size.

Each run is a whole cell run — pool, warm-up chunk, window through
``driver.core.run_chunk`` into sqlite, rows read back and compared with
the plain reference — with the look for a TPU skipped and the chips cut to
10x10 px over five years.  The fault runs break the timed path underneath
and must come out ``correct: false``.
"""

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
CELL = "landsat-ard-conus.breaks"
SEED = 2**33 + 5                       # wider than 32 bits, as the driver's


def tiny(cellspec, **driver):
    c = cellspec["config"]
    c["sensor"]["chip_side"] = 10
    c["acquired"] = "2000-01-01/2005-01-01"
    c["driver"].update(dict(chips_per_batch=2, max_obs=256,
                            device_sharding="off"), **driver)
    return cellspec


@pytest.fixture
def knobs(monkeypatch):
    def set_(cellspec):
        for k, v in cellspec["config"]["knobs"].items():
            monkeypatch.setenv(k, v)
        return cellspec
    return set_


def run(cellspec, trace=False):
    return harness.run(cellspec, SEED, 0.5, trace, time.perf_counter(),
                       require_tpu=False)


def over_limit(result):
    return sorted(k for k, v in result["checks"].items()
                  if v["value"] > v["limit"])


def test_sound_run_of_a_new_mix_file(tmp_path, knobs):
    """A mix dropped into mixes/ of a checkout runs as a cell of its own:
    only BENCHMARK.json gains an entry, no file of the harness changes."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    def digest():
        return {p: hashlib.sha256(p.read_bytes()).hexdigest()
                for p in sorted((root / "benchmark").rglob("*"))
                if p.is_file() and "__pycache__" not in p.parts}

    before = digest()
    (root / "benchmark" / "mixes" / "drought.json").write_text(json.dumps({
        "why": "a new mix: no fill, every pixel one late break",
        "change_frac": 1.0, "n_changes": 1, "cloud_frac": 0.3,
        "seasonal_gap_frac": 0.2, "fill_frac": 0.0}))
    spec["workloads"].append({"name": "landsat-ard-conus.drought",
                              "config": "landsat-ard-conus",
                              "traffic": "drought", "chips": 1,
                              "why": "test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = digest()
    assert {p for p in after if after[p] != before.get(p)} == \
        {root / "benchmark" / "mixes" / "drought.json"}

    cs = knobs(tiny(harness.load_cell(str(root),
                                      "landsat-ard-conus.drought")))
    r = run(cs)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["mismatch_px_pct"]["value"] == 0.0
    assert r["attempted"] >= 4 and r["attempted"] % 2 == 0
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"pixels_per_s", "setup_s"}
    assert r["metrics"]["pixels_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"


def _unchanged_state(monkeypatch):
    """The kernel's answer is its initial state: no segment, empty mask."""
    from firebird_tpu.driver import core

    orig = core.fetch_results

    def fetch_results(seg, worst=None):
        host = orig(seg, worst)
        return dataclasses.replace(
            host, n_segments=np.zeros_like(host.n_segments),
            mask=np.zeros_like(host.mask))

    monkeypatch.setattr(core, "fetch_results", fetch_results)


def _half_batch(monkeypatch):
    """Half of each batch's chips never reach the writer."""
    from firebird_tpu.driver import core

    orig = core.write_batch_frames

    def write_batch_frames(packed, host_seg, n_real, **kw):
        return orig(packed, host_seg, max(1, n_real // 2), **kw)

    monkeypatch.setattr(core, "write_batch_frames", write_batch_frames)


def _altered_answer(monkeypatch):
    """Every stored break day is one day late."""
    from firebird_tpu.ccd import format as ccdformat
    from firebird_tpu.utils import dates as dt

    orig = ccdformat.batch_frames

    def batch_frames(packed, seg, n_real=None):
        out = orig(packed, seg, n_real)
        for _, frames in out:
            b = frames["segment"]["bday"]
            frames["segment"]["bday"] = np.array(
                [v if v == "0001-01-01" else dt.to_iso(dt.to_ordinal(v) + 1)
                 for v in b], dtype=object)
        return out

    monkeypatch.setattr(ccdformat, "batch_frames", batch_frames)


def _nan_payload(suffix):
    """Every stored segment's ``<band><suffix>`` value is NaN; the
    decisions (days, curve QA, change probability) stay as computed."""
    def fault(monkeypatch):
        from firebird_tpu.ccd import format as ccdformat

        orig = ccdformat.batch_frames

        def batch_frames(packed, seg, n_real=None):
            out = orig(packed, seg, n_real)
            for _, frames in out:
                s = frames["segment"]
                for k in [k for k in s if k[2:] == suffix]:
                    if suffix == "coef":
                        col = np.empty(len(s[k]), dtype=object)
                        col[:] = [np.full(np.shape(v), np.nan)
                                  for v in s[k]]
                        s[k] = col
                    else:
                        s[k] = np.full(len(s[k]), np.nan)
            return out

        monkeypatch.setattr(ccdformat, "batch_frames", batch_frames)
    return fault


@pytest.mark.parametrize("fault, fails", [
    (_unchanged_state, "mismatch_px_pct"),
    (_half_batch, "missing_rows"),
    (_altered_answer, "mismatch_px_pct"),
    (_nan_payload("rmse"), "coef_gap"),
    (_nan_payload("mag"), "coef_gap"),
    (_nan_payload("int"), "coef_gap"),
    (_nan_payload("coef"), "coef_gap"),
], ids=["state-unchanged", "half-batch-left-out", "answer-altered",
        "nan-rmse", "nan-magnitude", "nan-intercept", "nan-coefficients"])
def test_broken_timed_path_is_not_correct(fault, fails, monkeypatch, knobs):
    cs = knobs(tiny(harness.load_cell(ROOT, CELL)))
    fault(monkeypatch)
    r = run(cs)
    assert r["correct"] is False
    assert fails in over_limit(r)
    json.loads(json.dumps(r, allow_nan=False))    # a valid JSON line


def test_dropped_acquisitions_fail_the_run(knobs):
    cs = knobs(tiny(harness.load_cell(ROOT, CELL), max_obs=64))
    with pytest.raises(harness.RunFailed, match="dropped"):
        run(cs)


def test_no_tpu_exits_nonzero_with_no_result(tmp_path):
    """On a machine without a TPU the command prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cells_and_metrics_resolve():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cs = harness.load_cell(ROOT, w["name"])
        assert {m["name"] for m in cs["end_to_end"]} == \
            {"pixels_per_s", "setup_s"}
        assert cs["per_layer"]
        for m in cs["per_layer"]:
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py"))
        lim = harness.limits(ROOT, w["name"])
        assert set(lim) == {"mismatch_px_pct", "coef_gap", "missing_rows"}


@pytest.mark.parametrize("seconds, C, batches", [
    (50, 4, 5), (50, 6, 4), (25, 4, 3), (1, 4, 2)])
def test_window_is_fixed_work_in_whole_batches(seconds, C, batches):
    """breaks holds 20 chips at run_seconds 50; a shorter trial scales it
    down, never under two batches, whatever the program's speed."""
    cs = harness.load_cell(ROOT, CELL)
    assert cs["spec"]["run_seconds"] == 50
    assert cs["mix"]["window_chips"] == 20
    assert harness.window_batches(cs, seconds, C) == batches


@pytest.mark.parametrize("field", ["coefs", "rmse", "mag"])
def test_a_non_finite_stored_value_has_no_passing_gap(field):
    from benchmark import compare

    seg = dict(sday=1, eday=2, bday=2, curqa=8, chprob=0,
               coefs=np.ones((7, 8)), rmse=np.ones(7), mag=np.ones(7))
    ref = dict(segments=[seg], mask=np.ones(3, np.uint8))
    bad = dict(segments=[dict(seg, **{field: np.full_like(seg[field],
                                                           np.nan)})],
               mask=ref["mask"])
    assert compare.numeric_gap(ref, ref) == 0.0
    out = compare.compare({"k": bad}, {"k": ref})
    assert out == {"mismatch_px_pct": 0.0, "coef_gap": compare.UNBOUNDED}
    json.dumps(out, allow_nan=False)
