"""Multi-sensor support: the kernel/packer generic over band layout and chip
geometry (BASELINE.json config #5 — Sentinel-2 12-band, 10 m, 300x300-pixel
chips), with Landsat ARD as the default spec."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firebird_tpu.ccd import format as ccdformat
from firebird_tpu.ccd import kernel, params
from firebird_tpu.ccd.sensor import (LANDSAT_ARD, SENSORS, SENTINEL2,
                                     chi2_thresholds)
from firebird_tpu.ccd.synthetic import means_amps
from firebird_tpu.ingest import SyntheticSource, pack
from firebird_tpu.ingest.packer import PackedChips
from firebird_tpu.parallel import make_mesh
from firebird_tpu.parallel.mesh import detect_sharded


def slice_pixels(p: PackedChips, n: int) -> PackedChips:
    return PackedChips(cids=p.cids, dates=p.dates,
                       spectra=p.spectra[:, :, :n, :], qas=p.qas[:, :n, :],
                       n_obs=p.n_obs, sensor=p.sensor)


def test_sensor_specs_consistent():
    assert LANDSAT_ARD.n_bands == params.NUM_BANDS
    assert LANDSAT_ARD.band_names == params.BAND_NAMES
    assert LANDSAT_ARD.detection_bands == params.DETECTION_BANDS
    assert LANDSAT_ARD.pixels == 10000
    assert SENTINEL2.n_bands == 12
    assert SENTINEL2.pixels == 90000
    assert SENTINEL2.thermal_bands == ()
    # both use 5 detection bands -> identical chi2 thresholds, equal to the
    # module constants pinned for the reference
    chg, out = chi2_thresholds(len(LANDSAT_ARD.detection_bands))
    assert chg == params.CHANGE_THRESHOLD
    assert out == params.OUTLIER_THRESHOLD
    assert chi2_thresholds(5) == (chg, out)
    # detection/tmask roles land on the right wavelengths
    names = SENTINEL2.band_names
    assert [names[i] for i in SENTINEL2.detection_bands] == \
        ["green", "red", "nir", "swir1", "swir2"]
    assert [names[i] for i in SENTINEL2.tmask_bands] == ["green", "swir1"]
    # one distinct segment-column prefix per band, Landsat's the reference's
    for s in SENSORS.values():
        assert len(set(s.store_prefixes)) == len(s.store_prefixes) \
            == s.n_bands
    assert LANDSAT_ARD.store_prefixes == ("bl", "gr", "re", "ni", "s1", "s2",
                                          "th")


def test_means_amps_sized_to_sensor():
    m, a = means_amps(SENTINEL2)
    assert m.shape == (12,) and a.shape == (12,)
    assert np.all(m > 0)
    from firebird_tpu.ccd import synthetic

    m7, a7 = means_amps(LANDSAT_ARD)
    np.testing.assert_array_equal(m7, synthetic.DEFAULT_MEANS)
    np.testing.assert_array_equal(a7, synthetic.DEFAULT_AMPS)


def test_s2_synthetic_chip_shape():
    src = SyntheticSource(seed=3, start="1995-01-01", end="1997-01-01",
                          sensor=SENTINEL2, change_frac=0.0, cloud_frac=0.1)
    c = src.chip(0, 0)
    T = c.dates.shape[0]
    assert c.spectra.shape == (12, T, 300, 300)
    assert c.qas.shape == (T, 300, 300)
    assert c.sensor == SENTINEL2


def test_s2_kernel_detects_step_change():
    """The kernel compiled for the S2 spec finds the break every pixel of a
    whole-chip step change carries, with no thermal screening."""
    src = SyntheticSource(seed=3, start="1995-01-01", end="2000-01-01",
                          sensor=SENTINEL2, change_frac=1.0, cloud_frac=0.1)
    p = slice_pixels(pack([src.chip(0, 0)], bucket=32), 96)
    seg = kernel.detect_packed(p, dtype=jnp.float64)
    nseg = np.asarray(seg.n_segments)[0]
    proc = np.asarray(seg.procedure)[0]
    assert np.all(proc == kernel.PROC_STANDARD)
    assert (nseg >= 2).mean() > 0.9         # break found almost everywhere
    one = kernel.chip_slice(seg, 0, to_host=True)
    rec = kernel.segments_to_records(one, p.dates[0][: int(p.n_obs[0])],
                                     pixel=0, sensor=SENTINEL2)
    assert set(SENTINEL2.band_names) <= set(rec["change_models"][0])
    assert rec["change_models"][0]["swir2"]["rmse"] > 0
    # a confirmed break: first segment has chprob 1
    assert rec["change_models"][0]["change_probability"] == 1.0


def test_s2_result_shapes_follow_band_count():
    src = SyntheticSource(seed=4, start="1995-01-01", end="1997-01-01",
                          sensor=SENTINEL2, change_frac=0.0)
    p = slice_pixels(pack([src.chip(3000, 0)], bucket=32), 16)
    seg = kernel.detect_packed(p, dtype=jnp.float64)
    assert seg.seg_rmse.shape[-1] == 12
    assert seg.seg_coef.shape[-2:] == (12, params.MAX_COEFS)
    assert seg.vario.shape[-1] == 12


def test_s2_pixel_coords_10m():
    src = SyntheticSource(seed=3, start="1995-01-01", end="1996-01-01",
                          sensor=SENTINEL2, change_frac=0.0)
    p = pack([src.chip(0, 30000)], bucket=16)
    xy = p.pixel_coords(0)
    assert xy.shape == (90000, 2)
    assert tuple(xy[0]) == (0, 30000)
    assert tuple(xy[1]) == (10, 30000)          # 10 m pixels
    assert tuple(xy[300]) == (0, 30000 - 10)    # row-major, 300-wide


def test_s2_sharded_over_mesh():
    """Config #5's point: the denser stack shards over the device mesh the
    same way — chip axis split, zero collectives."""
    src = SyntheticSource(seed=5, start="1995-01-01", end="2000-01-01",
                          sensor=SENTINEL2, change_frac=1.0, cloud_frac=0.1)
    chips = [src.chip(3000 * i, 0) for i in range(2)]
    p = slice_pixels(pack(chips, bucket=32), 64)
    mesh = make_mesh(n_devices=2)
    seg = detect_sharded(p, mesh, dtype=jnp.float64)
    nseg = np.asarray(seg.n_segments)
    assert nseg.shape == (2, 64)
    assert (nseg >= 2).mean() > 0.8


def test_mixed_sensor_pack_rejected():
    l = SyntheticSource(seed=1, start="1995-01-01", end="1996-01-01")
    s = SyntheticSource(seed=1, start="1995-01-01", end="1996-01-01",
                        sensor=SENTINEL2)
    try:
        pack([l.chip(0, 0), s.chip(0, 0)])
    except AssertionError as e:
        assert "sensor" in str(e)
    else:
        raise AssertionError("mixed-sensor pack must be rejected")


# ---------------------------------------------------------------------------
# Sentinel-2 rows: kernel -> format -> store, against the float64 reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def s2_batch():
    """Two pixel-sliced Sentinel-2 chips, every pixel breaking, and their
    float64 kernel result, fetched to the host."""
    src = SyntheticSource(seed=6, start="1995-01-01", end="2000-01-01",
                          sensor=SENTINEL2, change_frac=1.0, cloud_frac=0.1)
    p = slice_pixels(pack([src.chip(3000 * i, 30000) for i in range(2)],
                          bucket=32), 48)
    return p, jax.device_get(kernel.detect_packed(p, dtype=jnp.float64))


def _same(a, b):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, (list, tuple, np.ndarray)):
        return np.array_equal(np.asarray(a), np.asarray(b))
    return a == b or (a != a and b != b)


def test_s2_batch_frames_match_chip_frames(s2_batch):
    """At 12 bands the one-pass batch formatter equals the per-chip path
    on every column, and the segment rows carry the twelve prefixes."""
    p, host = s2_batch
    assert (np.asarray(host.n_segments) >= 2).any()     # breaks among them
    out = ccdformat.batch_frames(p, host)
    assert len(out) == 2
    for c, (_, frames) in enumerate(out):
        ref = ccdformat.chip_frames(p, c, kernel.chip_slice(host, c,
                                                             to_host=True))
        for table in ("chip", "pixel", "segment"):
            assert list(frames[table]) == list(ref[table])
            for col in ref[table]:
                assert all(_same(a, b) for a, b in zip(
                    frames[table][col], ref[table][col])), (table, col)
        assert [k[:-4] for k in frames["segment"] if k.endswith("coef")] \
            == list(SENTINEL2.store_prefixes)


def test_s2_stored_rows_agree_with_the_reference(s2_batch, tmp_path):
    """Every pixel's stored Sentinel-2 rows, written through the drivers'
    egress tail into sqlite, equal the float64 reference's records
    formatted under the same prefixes: decisions exactly, the model
    values to float64 rounding."""
    from firebird_tpu.ccd.reference import detect_sensor
    from firebird_tpu.driver import core
    from firebird_tpu.obs import metrics as obs_metrics
    from firebird_tpu.store import AsyncWriter, SqliteStore

    p, host = s2_batch
    obs_metrics.reset_registry()
    store = SqliteStore(str(tmp_path / "s2.db"), "ks")
    writer = AsyncWriter(store)
    try:
        core.write_batch_frames(p, host, 2, writer=writer)
        writer.flush()
        snap = obs_metrics.get_registry().snapshot()
        assert snap["gauges"]["store_segment_columns"] == 58
        rows = store.count("segment")
        assert snap["counters"]["store_values_written"] == \
            rows * 58 + 2 * 48 * 5 + 2 * 3
        P = host.n_segments.shape[1]
        for c in range(2):
            T = int(p.n_obs[c])
            dates = p.dates[c][:T]
            for i, (px, py) in enumerate(p.pixel_coords(c)[:P]):
                cx, cy = (int(v) for v in p.cids[c])
                key = dict(cx=cx, cy=cy, px=int(px), py=int(py))
                got = store.read("segment", key)
                want = ccdformat.format_records(
                    cx, cy, px, py, dates,
                    detect_sensor(dates, p.spectra[c, :, i, :T],
                                  p.qas[c, i, :T], SENTINEL2),
                    sensor=SENTINEL2)
                assert len(got["sday"]) == len(want)
                order = np.argsort(got["sday"], kind="stable")
                for j, w in zip(order, sorted(want,
                                              key=lambda r: r["sday"])):
                    for k in ("sday", "eday", "bday", "curqa"):
                        assert got[k][j] == w[k], (key, k)
                    for k, v in w.items():
                        if k[:-3] in SENTINEL2.store_prefixes \
                                or k[:-4] in SENTINEL2.store_prefixes \
                                or k == "chprob":
                            g = got[k][j]
                            if v is None:
                                assert g is None, (key, k)
                            else:
                                np.testing.assert_allclose(
                                    g, v, rtol=1e-6, atol=1e-6,
                                    err_msg=f"{key} {k}")
                mask = store.read("pixel", key)["mask"][0]
                assert list(mask) == list(want[0]["mask"])
    finally:
        writer.close()
        store.close()


@pytest.mark.parametrize("P, blocks", [(90000, 9), (10000, 1), (100, 1),
                                       (20001, 3)])
def test_lane_blocks(P, blocks):
    assert kernel.lane_blocks(P) == blocks
    assert P % blocks == 0 and P // blocks <= kernel.MAX_CHIP_LANES


def test_wide_chip_runs_as_lane_blocks_with_unchanged_rows(monkeypatch):
    """A chip wider than kernel.MAX_CHIP_LANES runs as equal lane blocks
    on the chip axis: every per-pixel field equals the one-block run's,
    and the chip's active lanes per round add up across its blocks."""
    src = SyntheticSource(seed=7, start="1995-01-01", end="2000-01-01",
                          sensor=SENTINEL2, change_frac=1.0, cloud_frac=0.1)
    p = slice_pixels(pack([src.chip(0, 0), src.chip(3000, 0)], bucket=32),
                     40)
    jax.clear_caches()
    whole = jax.device_get(kernel.detect_packed(p, dtype=jnp.float64))
    monkeypatch.setattr(kernel, "MAX_CHIP_LANES", 16)
    assert kernel.lane_blocks(40) == 4
    jax.clear_caches()
    try:
        split = jax.device_get(kernel.detect_packed(p, dtype=jnp.float64))
    finally:
        jax.clear_caches()
    for f in ("n_segments", "seg_meta", "seg_rmse", "seg_mag", "seg_coef",
              "mask", "procedure", "vario"):
        np.testing.assert_array_equal(getattr(split, f), getattr(whole, f),
                                      err_msg=f)
    assert (np.asarray(whole.n_segments) >= 2).all()
    np.testing.assert_array_equal(split.rounds, whole.rounds)
    np.testing.assert_array_equal(split.occupancy[..., 0],
                                  whole.occupancy[..., 0])
