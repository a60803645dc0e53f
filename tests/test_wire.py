"""Wire diet (ISSUE 11): device-built designs, all-integer ingress, and
int-coded egress.

Three load-bearing contracts:

1. **Golden egress identity** — draining a batch through the int-coded
   egress path (FIREBIRD_WIRE_EGRESS=1: device pack_egress, depth
   slicing, host decode) writes store rows BYTE-IDENTICAL to the raw
   f32 drain (mirror of the compaction on/off golden test).
2. **Device designs match the host spec** — kernel.device_designs
   reproduces harmonic.design_matrix to f32 tolerance (and the phase
   argument exactly; only trig ulp differs).
3. **No float crosses the wire** — every staged ingress plane and every
   packed egress table is integer-dtyped.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firebird_tpu.ccd import format as ccdformat
from firebird_tpu.ccd import harmonic, kernel, params
from firebird_tpu.driver import core
from firebird_tpu.ingest import SyntheticSource, pack
from firebird_tpu.ingest.packer import PackedChips
from firebird_tpu.obs import Counters
from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.store import AsyncWriter, MemoryStore


@pytest.fixture(scope="module")
def batch():
    """2 pixel-sliced chips with breaks (so segment depth varies) plus
    the f32 kernel result — the egress golden surface."""
    src = SyntheticSource(seed=5, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1, change_frac=0.5)
    p = pack([src.chip(100 + 3000 * i, 200) for i in range(2)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :96, :],
                    qas=p.qas[:, :96, :], n_obs=p.n_obs)
    seg = kernel.detect_packed(p, dtype=jnp.float32)
    return p, seg


# ---------------------------------------------------------------------------
# 1. golden: int-coded egress writes byte-identical store rows
# ---------------------------------------------------------------------------

def _drain_to_store(seg, p, egress: str, monkeypatch, prepack=False):
    """Drain ``seg`` into a fresh store with the egress diet ``egress``;
    ``prepack`` hands the drain the payload packed beforehand, as the
    batch driver does at dispatch."""
    monkeypatch.setenv("FIREBIRD_WIRE_EGRESS", egress)
    result = core.pack_results(seg) if prepack else seg
    store = MemoryStore(f"wire{egress}")
    writer = AsyncWriter(store)
    try:
        core.drain_batch(result, p, p.n_chips, writer=writer,
                         counters=Counters(), dtype=jnp.float32)
        writer.flush()
    finally:
        writer.close()
    return store


def _assert_same_rows(on, off):
    for table in ("chip", "pixel", "segment"):
        rows_on, rows_off = on._tables[table], off._tables[table]
        assert set(rows_on) == set(rows_off), table
        for key in rows_off:
            assert rows_on[key] == rows_off[key], (table, key)


def test_golden_int_egress_store_rows_identical(batch, monkeypatch):
    """THE acceptance golden: every table row the int-coded drain lands
    equals the raw-f32 drain's row exactly — same keys, same cells."""
    p, seg = batch
    on = _drain_to_store(seg, p, "1", monkeypatch)
    off = _drain_to_store(seg, p, "0", monkeypatch)
    _assert_same_rows(on, off)
    assert on.count("segment") >= p.n_chips * 96


def _segments_at_depth(seg, depth: int, seed: int):
    """A float32 batched result of ``seg``'s shapes whose deepest pixel
    closed exactly ``depth`` segments: ordinal days in slot order,
    change probabilities k/PEEK_SIZE, arbitrary finite floats in the
    bitcast planes."""
    rng = np.random.default_rng(seed)
    C, P, S, _ = seg.seg_meta.shape
    T = seg.mask.shape[-1]
    n = rng.integers(0, depth + 1, (C, P)).astype(np.int32)
    n[0, 0] = depth
    meta = np.zeros((C, P, S, 6), np.float32)
    meta[..., 0] = 725000 + 400 * np.arange(S) \
        + rng.integers(0, 100, (C, P, S))
    meta[..., 1] = meta[..., 0] + rng.integers(100, 300, (C, P, S))
    meta[..., 2] = meta[..., 1] + 1
    meta[..., 3] = rng.integers(0, params.PEEK_SIZE + 1, (C, P, S)
                                ).astype(np.float32) \
        / np.float32(params.PEEK_SIZE)
    meta[..., 4] = rng.integers(0, 100, (C, P, S))
    meta[..., 5] = rng.integers(12, 400, (C, P, S))
    f = lambda *shape: rng.normal(0, 50, shape).astype(np.float32)
    return kernel.ChipSegments(
        n_segments=jnp.asarray(n), seg_meta=jnp.asarray(meta),
        seg_rmse=jnp.asarray(f(C, P, S, 7)),
        seg_mag=jnp.asarray(f(C, P, S, 7)),
        seg_coef=jnp.asarray(f(C, P, S, 7, 8)),
        mask=jnp.asarray(rng.random((C, P, T)) < 0.7),
        procedure=jnp.asarray(rng.integers(0, 4, (C, P)).astype(np.int32)),
        vario=jnp.asarray(f(C, P, 7)))


@pytest.mark.parametrize("depth", [1, 2, 3, 7, 10])
def test_golden_packed_at_capacity_fetched_to_depth(batch, depth,
                                                    monkeypatch):
    """Packed at full capacity (at dispatch), fetched as the first
    egress_bucket(depth) slot buffers: the store rows equal the raw-f32
    drain's byte for byte, and the wire carries exactly the int-coded
    budget at that depth — int32 per segment value for s_eff slots, a
    bit per mask date, int32 per-pixel tables."""
    p, real = batch
    seg = _segments_at_depth(real, depth, seed=depth)
    C, P, S, _ = real.seg_meta.shape
    T = real.mask.shape[-1]
    s_eff = kernel.egress_bucket(depth, S)
    obs_metrics.reset_registry()
    try:
        on = _drain_to_store(seg, p, "1", monkeypatch, prepack=True)
        d2h = obs_metrics.get_registry().snapshot()["counters"][
            "wire_d2h_bytes"]
    finally:
        obs_metrics.reset_registry()
    off = _drain_to_store(seg, p, "0", monkeypatch)
    _assert_same_rows(on, off)
    # every closed segment landed, and a sentinel row for empty pixels
    assert on.count("segment") == int(
        np.maximum(np.asarray(seg.n_segments), 1).sum())
    per_px = 4 + 4 + (T + 7) // 8 + 7 * 4 + s_eff * (6 + 7 + 7 + 56) * 4
    assert d2h == C * P * per_px


def test_pack_unpack_roundtrip_bit_exact(batch):
    """pack_egress -> decode_egress reproduces every result field bit
    for bit (at the fetched depth), and ships only integer tables: one
    buffer per segment slot at full capacity, of which the drain fetches
    the first egress_bucket(worst)."""
    p, seg = batch
    raw = jax.device_get(seg)
    worst = int(raw.n_segments.max())
    S = raw.seg_meta.shape[-2]
    s_eff = kernel.egress_bucket(worst, S)
    packed = kernel.pack_egress(seg)
    for k in kernel.EGRESS_SLOT_PLANES:
        assert len(packed[k]) == S, k
    tables = jax.device_get(kernel.egress_slots(packed, s_eff))
    for k in kernel.EGRESS_SLOT_PLANES:
        assert len(tables[k]) == s_eff, k
    leaves = jax.tree_util.tree_leaves(tables)
    assert all(v.dtype.kind in "iu" for v in leaves), \
        {str(v.dtype) for v in leaves}
    dec = ccdformat.decode_egress(tables, raw.mask.shape[-1])
    np.testing.assert_array_equal(dec.n_segments, raw.n_segments)
    np.testing.assert_array_equal(dec.procedure, raw.procedure)
    np.testing.assert_array_equal(dec.mask, raw.mask)
    np.testing.assert_array_equal(dec.vario, raw.vario)
    np.testing.assert_array_equal(dec.occupancy, raw.occupancy)
    for f in ("seg_meta", "seg_rmse", "seg_mag", "seg_coef"):
        np.testing.assert_array_equal(
            np.asarray(getattr(dec, f)),
            np.asarray(getattr(raw, f))[:, :, :s_eff], err_msg=f)
        assert getattr(dec, f).dtype == np.float32, f


def test_egress_bucket_depths():
    assert kernel.egress_bucket(1, 10) == 1
    assert kernel.egress_bucket(2, 10) == 2
    assert kernel.egress_bucket(3, 10) == 4
    assert kernel.egress_bucket(7, 10) == 8
    assert kernel.egress_bucket(9, 10) == 10     # capped at capacity
    assert kernel.egress_bucket(0, 10) == 1      # no segments: one slot


def test_chprob_count_coding_is_lossless():
    """Every chprob value the kernel can emit (k/PEEK_SIZE and the
    exact 1.0 of a confirmed break) survives the int coding bit-exactly
    — the coding contract pack_egress's meta column relies on."""
    vals = np.array([k / params.PEEK_SIZE
                     for k in range(params.PEEK_SIZE + 1)] + [1.0, 0.0],
                    np.float32)
    coded = np.rint(vals * params.PEEK_SIZE).astype(np.int32)
    decoded = coded.astype(np.float32) / np.float32(params.PEEK_SIZE)
    np.testing.assert_array_equal(decoded, vals)


# ---------------------------------------------------------------------------
# 2. device-built designs match the host float64 spec
# ---------------------------------------------------------------------------

def test_device_designs_match_host_f32_tol(batch):
    """kernel.device_designs == harmonic.design_matrix to f32 tolerance
    (the satellite contract): the exact-integer phase reduction keeps
    the phase argument bit-identical; only trig evaluation differs, by
    trig ulp."""
    p, _ = batch
    Xs, Xts, ts, valids = kernel.device_designs(
        jnp.asarray(p.dates, jnp.int32), jnp.asarray(p.n_obs, jnp.int32),
        jnp.float32)
    hXs, hXts, hvalid = kernel.prep_batch(p)
    np.testing.assert_allclose(np.asarray(Xs), hXs, atol=3e-6, rtol=3e-6)
    np.testing.assert_allclose(np.asarray(Xts), hXts, atol=3e-6,
                               rtol=3e-6)
    np.testing.assert_array_equal(np.asarray(valids), hvalid)
    np.testing.assert_array_equal(np.asarray(ts)[:, :int(p.n_obs[0])],
                                  p.dates[:, :int(p.n_obs[0])])
    # padding rows zeroed, exactly like build_designs' rule
    T = p.dates.shape[1]
    for c in range(p.n_chips):
        n = int(p.n_obs[c])
        if n < T:
            assert not np.asarray(Xs)[c, n:].any()


def test_device_designs_phase_is_exact():
    """The phase argument (t mod 365.25) is exact integer arithmetic —
    bit-identical to the float64 np.mod for any ordinal day, in f32."""
    days = np.arange(690000, 740000, 367, np.int32)[None]
    n = np.array([days.shape[1]], np.int32)
    # reconstruct the device phase computation
    quarter = np.mod(4 * days.astype(np.int64), 1461)
    dev_phase = quarter.astype(np.float32) * np.float32(0.25)
    host_phase = np.mod(days.astype(np.float64), 365.25)
    np.testing.assert_array_equal(dev_phase[0].astype(np.float64),
                                  host_phase[0])
    del n


def test_wire_detect_matches_host_design_detect(batch):
    """Structural safety: running the kernel with device-built designs
    flips no decisions vs the host-built designs on this workload (the
    trig-ulp perturbation is far inside the decision envelope)."""
    p, seg = batch
    Xs, Xts, valid = kernel.prep_batch(p)
    ref = kernel._detect_batch_core(
        jnp.asarray(Xs, jnp.float32), jnp.asarray(Xts, jnp.float32),
        jnp.asarray(p.dates, jnp.float32), jnp.asarray(valid),
        jnp.asarray(p.spectra), jnp.asarray(p.qas, jnp.int32),
        wcap=kernel.window_cap(p), dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(seg.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_array_equal(
        np.round(np.asarray(seg.seg_meta)[..., [0, 1, 2, 4, 5]]),
        np.round(np.asarray(ref.seg_meta)[..., [0, 1, 2, 4, 5]]))


# ---------------------------------------------------------------------------
# 3. the wire is all-integer, and the counters see it
# ---------------------------------------------------------------------------

def test_staged_ingress_planes_are_integer(batch):
    p, _ = batch
    args = kernel.wire_args(p)
    dts = [np.dtype(a.dtype) for a in args]
    assert all(d.kind in "iu" for d in dts), dts
    assert dts[0] == np.int32 and dts[1] == np.int32
    assert dts[2] == np.int16
    assert dts[3] == (np.uint8 if kernel.wire_qa8() else np.uint16)


def test_qa8_wire_matches_u16(batch, monkeypatch):
    """The uint8 QA wire is lossless for detection: identical results
    vs the full uint16 plane (triage reads bits 0-5 only)."""
    p, _ = batch
    monkeypatch.setenv("FIREBIRD_WIRE_QA8", "0")
    wide = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_WIRE_QA8", "1")
    narrow = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(narrow.n_segments),
                                  np.asarray(wide.n_segments))
    np.testing.assert_array_equal(np.asarray(narrow.seg_meta),
                                  np.asarray(wide.seg_meta))
    np.testing.assert_array_equal(np.asarray(narrow.mask),
                                  np.asarray(wide.mask))


def test_wire_counters_and_packed_d2h(batch, monkeypatch):
    """wire_h2d_bytes counts the integer staging; wire_d2h_bytes counts
    the PACKED drain — strictly smaller than the raw f32 result."""
    p, seg = batch
    obs_metrics.reset_registry()
    monkeypatch.setenv("FIREBIRD_WIRE_EGRESS", "1")
    staged = core.stage_batch(p, jnp.float32, "off")
    store = MemoryStore("wc")
    writer = AsyncWriter(store)
    try:
        core.drain_batch(seg, p, p.n_chips, writer=writer,
                         counters=Counters(), dtype=jnp.float32)
        writer.flush()
    finally:
        writer.close()
    snap = obs_metrics.get_registry().snapshot()["counters"]
    h2d = snap["wire_h2d_bytes"]
    d2h = snap["wire_d2h_bytes"]
    assert h2d == sum(a.nbytes for a in staged.args)
    raw_bytes = int(sum(np.asarray(v).nbytes for v in
                        jax.tree_util.tree_leaves(jax.device_get(seg))))
    assert 0 < d2h < raw_bytes / 2
    obs_metrics.reset_registry()


def test_f64_drain_keeps_raw_path(monkeypatch):
    """The f64 bit-parity path never routes through the f32 egress
    coding (pack_egress is f32-only by contract)."""
    src = SyntheticSource(seed=3, start="1995-01-01", end="1996-06-01")
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :32, :],
                    qas=p.qas[:, :32, :], n_obs=p.n_obs)
    seg = kernel.detect_packed(p, dtype=jnp.float64)
    monkeypatch.setenv("FIREBIRD_WIRE_EGRESS", "1")
    host = core.fetch_results(seg)
    assert np.asarray(host.seg_meta).dtype == np.float64
    np.testing.assert_array_equal(np.asarray(host.n_segments),
                                  np.asarray(seg.n_segments))


def test_warm_avatars_hit_real_dispatch_cache(compile_cache_at):
    """THE warm-start drift contract for the new signature: an AOT
    compile built from warm_start's avatar dtype tuple must be the
    persistent-cache entry a REAL staged dispatch of the same shape
    deserializes.  Any dtype drift between core.wire_avatar_dtypes and
    kernel.wire_args (e.g. a QA wire change on one side only) fails the
    equality below AND the cache-hit assertion."""
    try:
        assert core.setup_compile_cache() == compile_cache_at

        src = SyntheticSource(seed=3, start="1995-01-01",
                              end="1996-01-01")
        p = pack([src.chip(100, 200)], bucket=32)
        p = PackedChips(cids=p.cids, dates=p.dates,
                        spectra=p.spectra[:, :, :16, :],
                        qas=p.qas[:, :16, :], n_obs=p.n_obs)
        args_np = kernel.wire_args(p)
        # the one-definition contract: avatar dtypes == staged dtypes
        assert tuple(np.dtype(a.dtype) for a in args_np) \
            == tuple(np.dtype(d) for d in core.wire_avatar_dtypes())

        avatars = tuple(jax.ShapeDtypeStruct(a.shape, d)
                        for a, d in zip(args_np,
                                        core.wire_avatar_dtypes()))
        kernel.aot_compile(avatars, dtype=jnp.float32,
                           wcap=kernel.window_cap(p), sensor=p.sensor)
        assert os.listdir(compile_cache_at)        # AOT entry written
        jax.clear_caches()                         # force the cache path
        obs_metrics.reset_registry()
        seg = kernel.detect_packed(p, dtype=jnp.float32)
        assert np.asarray(seg.n_segments).shape == (1, 16)  # ran
        snap = obs_metrics.get_registry().snapshot()
        assert snap["counters"].get("compile_cache_hits", 0) > 0, \
            snap["counters"]
    finally:
        obs_metrics.reset_registry()
