"""Pallas CD-loop kernel: bit-level parity with the lax path (interpret
mode on CPU; the same kernel runs compiled on TPU under FIREBIRD_PALLAS=1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firebird_tpu.ccd import harmonic, kernel, params, pallas_ops


@pytest.fixture(autouse=True)
def _clear_pallas_env(monkeypatch):
    """Every parity test's reference run must trace the default XLA path:
    an ambient FIREBIRD_PALLAS (e.g. from a bench shell) would route both
    sides through the same kernels and make the comparison vacuous."""
    monkeypatch.delenv("FIREBIRD_PALLAS", raising=False)


def _systems(P=37, B=7, T=60, dtype=jnp.float32, seed=0):
    """Realistic (G, c, diag, mask) built exactly as _fit_lasso_coefs does."""
    rng = np.random.default_rng(seed)
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], params.MAX_COEFS), dtype)
    Y = jnp.asarray(rng.normal(1000, 300, (P, B, T)), dtype)
    w = jnp.asarray((rng.random((P, T)) < 0.8), dtype)
    K = params.MAX_COEFS
    n = jnp.maximum(jnp.sum(w, -1), 1.0)
    XX = (X[:, :, None] * X[:, None, :]).reshape(-1, K * K)
    G = (w @ XX).reshape(-1, K, K) / n[:, None, None]
    c = jnp.einsum("pbt,tc->pbc", Y * w[:, None, :], X) / n[:, None, None]
    diag = jnp.maximum(jnp.diagonal(G, axis1=-2, axis2=-1), 1e-12)
    nc = rng.choice([4, 6, 8], P)
    mask = jnp.asarray(np.arange(K)[None, :] < nc[:, None])
    return G, c, diag, mask


# The two CD implementations reduce over k in different association
# orders, so they differ at machine epsilon per update; 50 iterations of
# soft-thresholding amplify that slightly in f32.  Tolerances mirror the
# kernel-vs-oracle parity ladder (test_ccd_reference).
_TOL = {jnp.dtype(jnp.float32): dict(rtol=1e-2, atol=1e-2),
        jnp.dtype(jnp.float64): dict(rtol=1e-8, atol=1e-8)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_pallas_cd_matches_lax(dtype):
    G, c, diag, mask = _systems(dtype=dtype)
    ref = kernel._lasso_cd_lax(G, c, diag, mask)
    got = pallas_ops.lasso_cd(G, c, diag, mask, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               **_TOL[jnp.dtype(dtype)])


def test_pallas_cd_under_vmap():
    """The detect path calls the CD loop under vmap over chips."""
    Gs, cs, ds, ms = zip(*[_systems(P=16, dtype=jnp.float64, seed=s)
                           for s in range(3)])
    G, c, d, m = (jnp.stack(x) for x in (Gs, cs, ds, ms))
    ref = jax.vmap(kernel._lasso_cd_lax)(G, c, d, m)
    got = jax.vmap(lambda *a: pallas_ops.lasso_cd(*a, interpret=True))(
        G, c, d, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               **_TOL[jnp.dtype(jnp.float64)])


@pytest.mark.slow  # ~30-60s interpret-mode run; tier-1 (-m 'not slow') budget keeps the faster per-kernel parity rungs instead
def test_pallas_flag_routes_full_detect(monkeypatch):
    """FIREBIRD_PALLAS=1 routes the whole chip detector through the Pallas
    CD loop with results matching the default path."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    src = SyntheticSource(seed=21, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1)
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :48, :], qas=p.qas[:, :48, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float64)
    monkeypatch.setenv("FIREBIRD_PALLAS", "1")
    # distinct wcap avoids reusing the compiled default-path program
    got = kernel._detect_batch_wire(
        *(jnp.asarray(a) for a in _wire_args(p)),
        dtype=jnp.dtype(jnp.float64), wcap=kernel.window_cap(p) + 8,
        sensor=p.sensor)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_allclose(np.asarray(got.seg_meta),
                               np.asarray(ref.seg_meta), atol=1e-9)


def _wire_args(p):
    # The all-integer wire tuple (designs build on device).
    return kernel.wire_args(p)


@pytest.mark.slow  # ~27s interpret-mode run; tier-1 (-m 'not slow') keeps the lax sharded parity (test_parallel) + single-device Pallas rungs
def test_pallas_inside_sharded_detect(monkeypatch):
    """The sharded production path (shard_map over the mesh) composes with
    the Pallas CD loop: each shard runs its own single-device Mosaic call,
    so no SPMD partitioning rule is needed."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips
    from firebird_tpu.parallel import make_mesh
    from firebird_tpu.parallel.mesh import detect_sharded

    src = SyntheticSource(seed=21, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1)
    p = pack([src.chip(100 + 3000 * i, 200) for i in range(2)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :48, :], qas=p.qas[:, :48, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    mesh = make_mesh(n_devices=2)
    ref = detect_sharded(p, mesh, dtype=jnp.float64)
    monkeypatch.setenv("FIREBIRD_PALLAS", "1")
    # fresh trace: a bigger wcap changes the static args, busting the cache
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + 8)
    got = detect_sharded(p, mesh, dtype=jnp.float64)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_allclose(np.asarray(got.seg_meta),
                               np.asarray(ref.seg_meta), atol=1e-9)


def test_monitor_chain_matches_jnp_reference():
    """pallas_ops.monitor_chain (interpret mode) reproduces
    kernel._monitor_chain exactly on randomized round states — every
    output, including the argmax no-hit defaults and INF sentinels."""
    from firebird_tpu.ccd import pallas_ops

    rng = np.random.default_rng(5)
    P, T = 137, 96           # odd sizes force the block padding path
    for trial in range(4):
        alive = rng.random((P, T)) < 0.8
        s = jnp.asarray(
            rng.gamma(2.0, 6.0, (P, T)).astype(np.float32))
        included = jnp.asarray((rng.random((P, T)) < 0.4) & alive)
        rank = jnp.cumsum(jnp.asarray(alive), -1) - 1
        cur_k = jnp.asarray(rng.integers(0, T, P), jnp.int32)
        n_last_fit = jnp.asarray(rng.integers(1, 40, P), jnp.int32)
        in_mon = jnp.asarray(rng.random(P) < 0.7)
        alive = jnp.asarray(alive)
        kw = dict(change_thr=11.07, outlier_thr=15.09)
        want = kernel._monitor_chain(s, alive, included, rank, cur_k,
                                     n_last_fit, in_mon, **kw)
        got = pallas_ops.monitor_chain(s, alive, included, rank, cur_k,
                                       n_last_fit, in_mon, interpret=True,
                                       **kw)
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(want[k]), err_msg=k)


@pytest.mark.slow  # ~30-60s interpret-mode run; tier-1 (-m 'not slow') budget keeps the faster per-kernel parity rungs instead
def test_monitor_chain_in_detect_matches_default(monkeypatch):
    """FIREBIRD_PALLAS=1 routes the monitor chain (and the CD loop)
    through Pallas; full-detect results must equal the default path."""
    from firebird_tpu.ingest import SyntheticSource, pack

    src = SyntheticSource(seed=33, start="1995-01-01", end="1999-01-01",
                          cloud_frac=0.15)
    p = pack([src.chip(100, 200)], bucket=32)
    from firebird_tpu.ingest.packer import PackedChips
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :64, :], qas=p.qas[:, :64, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", "1")
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + 16)
    got = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_allclose(np.asarray(got.seg_meta),
                               np.asarray(ref.seg_meta), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(ref.mask))


def test_monitor_scored_matches_jnp_reference():
    """pallas_ops.monitor_chain_scored (interpret) reproduces the XLA
    score + kernel._monitor_chain pipeline on randomized round states,
    reading wire-dtype int16 detection-band spectra."""
    from firebird_tpu.ccd import harmonic, pallas_ops

    rng = np.random.default_rng(11)
    P, T, nb, K = 137, 96, 5, params.MAX_COEFS
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], K), jnp.float32)
    for trial in range(3):
        Yd = rng.integers(0, 8000, (nb, T, P)).astype(np.int16)
        coefs = jnp.asarray(rng.normal(0, 1, (P, nb, K)) * 100, jnp.float32)
        dden = jnp.asarray(np.abs(rng.normal(150, 40, (P, nb))) + 1,
                           jnp.float32)
        alive = rng.random((P, T)) < 0.8
        included = jnp.asarray((rng.random((P, T)) < 0.4) & alive)
        rank = jnp.cumsum(jnp.asarray(alive), -1) - 1
        cur_k = jnp.asarray(rng.integers(0, T, P), jnp.int32)
        n_last_fit = jnp.asarray(rng.integers(1, 40, P), jnp.int32)
        in_mon = jnp.asarray(rng.random(P) < 0.7)
        alive = jnp.asarray(alive)
        kw = dict(change_thr=11.07, outlier_thr=15.09)

        # the XLA path: [P,nb,T] prediction einsum -> score -> chain
        Yp = jnp.asarray(Yd.transpose(2, 0, 1), jnp.float32)   # [P,nb,T]
        pred = jnp.einsum("pbc,tc->pbt", coefs, X)
        s = jnp.sum(((Yp - pred) / dden[:, :, None]) ** 2, axis=1)
        want = kernel._monitor_chain(s, alive, included, rank, cur_k,
                                     n_last_fit, in_mon, **kw)
        got = pallas_ops.monitor_chain_scored(
            jnp.asarray(Yd), coefs, dden, X, alive, included, cur_k,
            n_last_fit, in_mon, interpret=True, **kw)
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(want[k]), err_msg=k)


def test_score_kernel_in_detect_matches_default(monkeypatch):
    """FIREBIRD_PALLAS=score routes the monitor score+chain through the
    score-fused kernel; segment decisions must equal the default path."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    src = SyntheticSource(seed=66, start="1995-01-01", end="1999-01-01",
                          cloud_frac=0.15)
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :64, :], qas=p.qas[:, :64, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", "score")
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + 40)
    got = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_array_equal(np.asarray(got.seg_meta[..., :3]),
                                  np.asarray(ref.seg_meta[..., :3]))
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(ref.mask))


def test_tmask_bad_matches_jnp_reference():
    """pallas_ops.tmask_bad (interpret) reproduces kernel._tmask_bad on
    randomized windows — including degenerate all-masked and constant
    (non-PD Gram -> NaN -> flag-nothing) pixels."""
    from firebird_tpu.ccd import pallas_ops

    rng = np.random.default_rng(9)
    P, W, nt = 153, 24, 5
    Xtw = rng.normal(0, 1, (P, W, nt)).astype(np.float32)
    Xtw[:, :, 0] = 1.0
    Y2 = (400 + 80 * rng.normal(0, 1, (P, 2, W))).astype(np.float32)
    # a few outliers the screen should flag
    Y2[rng.random((P, 2, W)) < 0.05] += 900
    nwin = rng.integers(0, W + 1, P)
    w = (np.arange(W)[None, :] < nwin[:, None]).astype(np.float32)
    vario2 = np.abs(rng.normal(40, 10, (P, 2))).astype(np.float32)
    Y2[7] = 444.0                      # constant series -> singular Gram
    want = np.asarray(kernel._tmask_bad(
        jnp.asarray(Xtw), jnp.asarray(Y2), jnp.asarray(w),
        jnp.asarray(vario2)))
    got = np.asarray(pallas_ops.tmask_bad(
        jnp.asarray(Xtw), jnp.asarray(Y2), jnp.asarray(w),
        jnp.asarray(vario2), interpret=True))
    assert want.any() and not want.all()
    np.testing.assert_array_equal(got, want)


def test_full_pallas_detect_matches_default(monkeypatch):
    """FIREBIRD_PALLAS=lasso,monitor,tmask routes all three components
    through Pallas; full-detect results must equal the default path."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    src = SyntheticSource(seed=44, start="1995-01-01", end="1999-01-01",
                          cloud_frac=0.2)
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :64, :], qas=p.qas[:, :64, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", "lasso,monitor,tmask")
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + 24)
    got = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_allclose(np.asarray(got.seg_meta),
                               np.asarray(ref.seg_meta), atol=1e-5)


@pytest.mark.slow  # ~61s W-unrolled interpret run; tier-1 (-m 'not slow') keeps test_full_pallas_detect_matches_default (the init block runs inside it) and `make test` / fuse-smoke still run this rung
def test_init_window_matches_init_block():
    """pallas_ops.init_window (interpret) reproduces kernel._init_block
    on randomized mid-loop round states, reading wire int16 spectra."""
    import functools
    from firebird_tpu.ccd import harmonic, pallas_ops
    from firebird_tpu.ccd.sensor import LANDSAT_ARD

    rng = np.random.default_rng(17)
    P, B, T, W = 137, 7, 96, 24
    t = np.float64(np.sort(rng.integers(729000, 730500, T)))
    X = jnp.asarray(harmonic.design_matrix(t, t[0], params.MAX_COEFS),
                    jnp.float32)
    Xt_full = harmonic.design_matrix(t, t[0], params.TMASK_COEFS + 1)
    Xt = jnp.asarray(np.concatenate([Xt_full[:, :1], Xt_full[:, 2:]], 1),
                     jnp.float32)
    Yi = rng.integers(0, 8000, (B, P, T)).astype(np.int16)
    Y = jnp.asarray(Yi.transpose(1, 0, 2), jnp.float32)       # [P,B,T]
    Yt = jnp.asarray(Yi.transpose(0, 2, 1))                   # [B,T,P] i16
    vario = jnp.asarray(np.abs(rng.normal(100, 30, (P, B))) + 1,
                        jnp.float32)
    alive = jnp.asarray(rng.random((P, T)) < 0.7)
    cur_i = jnp.asarray(rng.integers(0, T // 2, P), jnp.int32)
    phase = jnp.asarray(
        rng.choice([kernel.PHASE_INIT, kernel.PHASE_MONITOR,
                    kernel.PHASE_DONE], P, p=[0.6, 0.2, 0.2]), jnp.int32)

    res = dict(X=X, Xt=Xt, t=jnp.asarray(t, jnp.float32), Y=Y, Yt=Yt,
               XX=(X[:, :, None] * X[:, None, :]).reshape(T, -1),
               vario=vario)
    st = dict(alive=alive, cur_i=cur_i, phase=phase)
    fit = functools.partial(kernel._fit_chip, fit_pallas=False,
                            on_tpu=False)
    want = kernel._init_block(res, st, sensor=LANDSAT_ARD, W=W,
                              fdtype=jnp.float32, fit=fit, f32_ok=True)
    got = pallas_ops.init_window(alive, cur_i, phase == kernel.PHASE_INIT,
                                 res["t"], X, Xt, Yt, vario, W=W,
                                 sensor=LANDSAT_ARD, interpret=True)
    assert set(got) == set(want)
    # integer/boolean outputs must agree exactly; the stability verdict
    # (init_ok/init_bad) depends on an f32 fit whose Gram accumulation
    # order differs between the XLA dot and the kernel core, so allow a
    # tiny borderline disagreement there (none observed on this seed).
    exact = ["init_nowin", "init_tm", "has_adv", "i_next_tm", "i_adv",
             "j", "alive_init", "w_stab", "n_ok"]
    for k in exact:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(want[k]), err_msg=k)
    for k in ["init_ok", "init_bad"]:
        diff = np.mean(np.asarray(got[k]) != np.asarray(want[k]))
        assert diff <= 0.02, (k, diff)


@pytest.mark.slow  # ~30-60s interpret-mode run; tier-1 (-m 'not slow') budget keeps the faster per-kernel parity rungs instead
def test_init_kernel_in_detect_matches_default(monkeypatch):
    """FIREBIRD_PALLAS=init routes the whole INIT block through the fused
    window kernel; segment decisions must equal the default path."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    src = SyntheticSource(seed=77, start="1995-01-01", end="1999-01-01",
                          cloud_frac=0.15)
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :64, :], qas=p.qas[:, :64, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", "init")
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + 48)
    got = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_array_equal(np.asarray(got.seg_meta[..., :3]),
                                  np.asarray(ref.seg_meta[..., :3]))
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(ref.mask))


@pytest.mark.slow  # ~30-60s interpret-mode run; tier-1 (-m 'not slow') budget keeps the faster per-kernel parity rungs instead
def test_full_pallas_sentinel2_matches_default(monkeypatch):
    """All Pallas components under the 12-band Sentinel-2 sensor layout:
    the bench's S2 rung runs with the autotuned FIREBIRD_PALLAS set, so
    every kernel must be sensor-generic (band counts, detection/Tmask
    subsets, no thermal)."""
    from firebird_tpu.ccd.sensor import SENTINEL2
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    src = SyntheticSource(seed=88, start="2019-01-01", end="2021-01-01",
                          cloud_frac=0.15, sensor=SENTINEL2)
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :64, :], qas=p.qas[:, :64, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", "1")
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + 56)
    got = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_array_equal(np.asarray(got.seg_meta[..., :3]),
                                  np.asarray(ref.seg_meta[..., :3]))
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(ref.mask))


def test_use_pallas_component_parsing(monkeypatch):
    for env, lasso, monitor, tmask in [
            ("0", False, False, False), ("", False, False, False),
            ("1", True, True, True),
            ("lasso", True, False, False),
            ("monitor,tmask", False, True, True),
            (" lasso , tmask ", True, False, True)]:
        monkeypatch.setenv("FIREBIRD_PALLAS", env)
        assert kernel.use_pallas("lasso") is lasso, env
        assert kernel.use_pallas("monitor") is monitor, env
        assert kernel.use_pallas("tmask") is tmask, env


def test_pallas_fit_matches_fit_lasso():
    """pallas_ops.lasso_fit (interpret) matches kernel._fit_lasso on the
    same systems, reading wire-dtype int16 spectra (widened in-register,
    exact)."""
    from firebird_tpu.ccd import harmonic, pallas_ops

    rng = np.random.default_rng(3)
    P, B, T = 141, 7, 60
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], params.MAX_COEFS),
                    jnp.float32)
    Yi = rng.integers(0, 8000, (P, B, T)).astype(np.int16)
    w = jnp.asarray((rng.random((P, T)) < 0.8), jnp.float32)
    nc = rng.choice([4, 6, 8], P)
    mask = jnp.asarray(np.arange(8)[None, :] < nc[:, None])
    ref_b, ref_r = kernel._fit_lasso(X, jnp.asarray(Yi, jnp.float32), w,
                                     mask)
    Yt = jnp.asarray(Yi.transpose(1, 2, 0))           # [B,T,P] int16
    got_b, got_r = pallas_ops.lasso_fit(Yt, w, X, mask, with_rmse=True,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got_b), np.asarray(ref_b),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(ref_r),
                               rtol=1e-2, atol=1e-2)
    nb, nr = pallas_ops.lasso_fit(Yt, w, X, mask, with_rmse=False,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(got_b))
    assert not np.asarray(nr).any()


@pytest.mark.slow  # ~65s interpret-mode run; tier-1 (-m 'not slow') keeps test_pallas_fit_matches_fit_lasso + the guarded-fit rungs and `make test` / fuse-smoke still run the fit-in-detect route
def test_fit_kernel_in_detect_matches_default(monkeypatch):
    """FIREBIRD_PALLAS=fit routes all three batched Lasso fits through the
    fused Pallas kernel; segment decisions must equal the default path."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    src = SyntheticSource(seed=55, start="1995-01-01", end="1999-01-01",
                          cloud_frac=0.15)
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :64, :], qas=p.qas[:, :64, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", "fit")
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + 32)
    got = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_array_equal(np.asarray(got.seg_meta[..., :3]),
                                  np.asarray(ref.seg_meta[..., :3]))
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(ref.mask))


@pytest.mark.slow  # ~67s, the suite's single heaviest test; tier-1 keeps the per-kernel mega rungs (init/fit/tmask parity) and `make test` / fuse-smoke still run the full mega-vs-core equality
def test_detect_mega_matches_batch_core(monkeypatch):
    """FIREBIRD_PALLAS=mega routes the ENTIRE event loop through the
    whole-loop kernel (one pallas_call, VMEM-resident spectra, per-block
    while_loop) and reproduces the default XLA loop's decisions on a
    break/spike/QA-mixed workload spanning multiple pixel blocks."""
    from firebird_tpu.ccd import synthetic
    from firebird_tpu.ccd.sensor import LANDSAT_ARD
    from firebird_tpu.ccd import pallas_ops

    # Force 2 pixel blocks so block-boundary/padding paths execute
    # (production BP would be >= the whole test chip).
    monkeypatch.setattr(pallas_ops, "mega_block_p",
                        lambda *a, **k: 128)

    rng = np.random.default_rng(31)
    C, B, P, T = 2, 7, 200, 72
    t = np.stack([np.sort(rng.integers(724000, 724000 + 9000, T)).astype(
        np.float64) for _ in range(C)])
    X = np.stack([harmonic.design_matrix(t[c], t[c, 0], params.MAX_COEFS)
                  for c in range(C)])
    Xt_full = np.stack([harmonic.design_matrix(t[c], t[c, 0],
                                               params.TMASK_COEFS + 1)
                        for c in range(C)])
    Xt = np.concatenate([Xt_full[:, :, :1], Xt_full[:, :, 2:]], -1)
    valid = np.ones((C, T), bool)
    Y = (rng.integers(400, 3000, (C, 1, P, 1))
         + rng.normal(0, 50, (C, B, P, T)))
    # step changes on half the pixels (break + re-init path), spikes on
    # a few (Tmask/outlier path)
    for c in range(C):
        for p_ in range(0, P, 2):
            cpos = rng.integers(T // 3, 2 * T // 3)
            Y[c, :, p_, cpos:] += rng.choice([-1.0, 1.0]) * rng.uniform(
                400, 1200)
        for p_ in range(0, P, 7):
            s = rng.integers(0, T - 1)
            Y[c, :, p_, s] += 2500
    Y = Y.astype(np.int16)
    qa = np.full((C, P, T), 1 << params.QA_CLEAR_BIT, np.int32)
    # some cloudy/fill lanes -> alt procedures + padded-lane inertness
    qa[:, P - 8:, ::2] = 1 << params.QA_CLOUD_BIT
    qa[:, P - 3:, :] = 1 << params.QA_FILL_BIT

    args = (jnp.asarray(X, jnp.float32), jnp.asarray(Xt, jnp.float32),
            jnp.asarray(t, jnp.float32), jnp.asarray(valid),
            jnp.asarray(Y), jnp.asarray(qa))

    ref = kernel._detect_batch_core(*args, wcap=24, dtype=jnp.float32)
    rn = np.asarray(ref.n_segments)

    monkeypatch.setenv("FIREBIRD_PALLAS", "mega")
    jax.clear_caches()
    try:
        got = kernel._detect_batch_core(*args, wcap=24, dtype=jnp.float32)
        gn = np.asarray(got.n_segments)
    finally:
        jax.clear_caches()

    # DECISION-EXACT agreement, no tolerated fraction (VERDICT r3 #3):
    # mega composes the same values-based _init_logic/_mon_scored_logic/
    # _gram_cd_core/_close_logic blocks as the XLA loop, and measured
    # agreement on this fixture is bit-exact across ALL seg_meta fields
    # in both variogram modes (tools/mega_diag.py) — the old >=98%/2e-4
    # envelope was stale conservatism from the pre-shared-logic kernel.
    # Segment counts, processing masks, and the day-valued decisions
    # (sday/eday/bday) plus curqa/nobs must be EQUAL on every pixel;
    # float diagnostics (chprob col 3, rmse, mag) get a tight envelope
    # so the pin survives a platform whose compiled accumulation order
    # differs in the last ulp without weakening any decision.
    np.testing.assert_array_equal(gn, rn)
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(ref.mask))
    m_r, m_g = np.asarray(ref.seg_meta), np.asarray(got.seg_meta)
    np.testing.assert_array_equal(m_g[..., [0, 1, 2, 4, 5]],
                                  m_r[..., [0, 1, 2, 4, 5]])   # days/qa/nobs
    np.testing.assert_allclose(m_g[..., 3], m_r[..., 3], atol=1e-5)  # chprob
    # rmse is a float diagnostic, not a decision: the two routes reduce
    # the residual sums in different orders (measured max rel diff
    # 1.8e-5 under conftest x64; decisions above are still exact).
    np.testing.assert_allclose(np.asarray(got.seg_rmse),
                               np.asarray(ref.seg_rmse), rtol=1e-4)
    # mag is a median over scored residuals: an ulp input difference can
    # flip WHICH element lands in the median slot when two are nearly
    # equal, so the output jumps by the inter-element gap (measured: 7 of
    # 28000 elements, max 5.6e-3 on noise-scale values), not an ulp.
    np.testing.assert_allclose(np.asarray(got.seg_mag),
                               np.asarray(ref.seg_mag), rtol=5e-3, atol=1e-2)
    np.testing.assert_allclose(
        np.asarray(got.vario), np.asarray(ref.vario), rtol=1e-6)


@pytest.mark.slow  # ~60s interpret-mode run; tier-1 (-m 'not slow') keeps test_detect_mega_matches_batch_core as the mega-route parity rung
def test_detect_mega_sentinel2_and_capacity(monkeypatch):
    """Band-layout genericity + the overflow contract on the mega route:
    the 12-band Sentinel-2 kernel (different detection/tmask sets, no
    thermal) reproduces the XLA loop, and a deliberately tiny
    max_segments still COUNTS every close (writes past capacity drop) so
    detect_packed's capacity retry can see the overflow."""
    from firebird_tpu.ccd import synthetic
    from firebird_tpu.ccd.sensor import SENTINEL2

    rng = np.random.default_rng(13)
    C, P, T = 1, 96, 64
    B = SENTINEL2.n_bands
    t = np.stack([np.sort(rng.integers(737000, 737000 + 5500, T)).astype(
        np.float64) for _ in range(C)])
    X = np.stack([harmonic.design_matrix(t[c], t[c, 0], params.MAX_COEFS)
                  for c in range(C)])
    Xt_full = np.stack([harmonic.design_matrix(t[c], t[c, 0],
                                               params.TMASK_COEFS + 1)
                        for c in range(C)])
    Xt = np.concatenate([Xt_full[:, :, :1], Xt_full[:, :, 2:]], -1)
    valid = np.ones((C, T), bool)
    Y = (rng.integers(400, 3000, (C, 1, P, 1))
         + rng.normal(0, 50, (C, B, P, T)))
    for p_ in range(0, P, 2):       # a step change on half the pixels
        cpos = rng.integers(T // 3, 2 * T // 3)
        Y[0, :, p_, cpos:] += rng.uniform(400, 1200)
    Y = Y.astype(np.int16)
    qa = np.full((C, P, T), 1 << params.QA_CLEAR_BIT, np.int32)

    args = (jnp.asarray(X, jnp.float32), jnp.asarray(Xt, jnp.float32),
            jnp.asarray(t, jnp.float32), jnp.asarray(valid),
            jnp.asarray(Y), jnp.asarray(qa))
    kw = dict(wcap=24, dtype=jnp.float32, sensor=SENTINEL2)

    ref = kernel._detect_batch_core(*args, **kw)
    rn = np.asarray(ref.n_segments)

    monkeypatch.setenv("FIREBIRD_PALLAS", "mega")
    jax.clear_caches()
    try:
        got = kernel._detect_batch_core(*args, **kw)
        gn = np.asarray(got.n_segments)
        # capacity 1: closes past the first must still be COUNTED even
        # though their rows drop (the overflow-retry contract)
        tiny = kernel._detect_batch_core(*args, max_segments=1, **kw)
        tn = np.asarray(tiny.n_segments)
    finally:
        jax.clear_caches()

    assert np.mean(rn != gn) <= 0.02, np.mean(rn != gn)
    same = rn == gn
    m_r, m_g = np.asarray(ref.seg_meta), np.asarray(got.seg_meta)
    agree = np.isclose(m_r, m_g, atol=2e-4).all(-1).all(-1)[same].mean()
    assert agree >= 0.98, agree
    np.testing.assert_array_equal(tn, gn)          # counts don't saturate
    # the one in-capacity row equals the full run's first row
    np.testing.assert_allclose(
        np.asarray(tiny.seg_meta)[:, :, 0], m_g[:, :, 0], atol=1e-6)


@pytest.mark.slow  # ~30-60s interpret-mode run; tier-1 (-m 'not slow') budget keeps the faster per-kernel parity rungs instead
def test_mega_inside_sharded_detect(monkeypatch):
    """The sharded production path (shard_map over the mesh) composes
    with the whole-loop mega kernel: each shard runs its own
    single-device pallas_call (grid over its chip shard x pixel blocks),
    so no SPMD partitioning rule is needed.  f32: the mega route is
    gated f32-only, so an f64 dispatch would silently fall back to the
    XLA loop and make this test vacuous."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips
    from firebird_tpu.parallel import make_mesh
    from firebird_tpu.parallel.mesh import detect_sharded

    src = SyntheticSource(seed=21, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1)
    p = pack([src.chip(100 + 3000 * i, 200) for i in range(2)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :48, :], qas=p.qas[:, :48, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    mesh = make_mesh(n_devices=2)
    ref = detect_sharded(p, mesh, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", "mega")
    jax.clear_caches()
    try:
        got = detect_sharded(p, mesh, dtype=jnp.float32)
    finally:
        jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_allclose(np.asarray(got.seg_meta),
                               np.asarray(ref.seg_meta), atol=2e-4)


# ---------------------------------------------------------------------------
# Per-block skip guards (active-lane compaction, ISSUE 6): a block with
# no active lane must cost only its predicate + zero-fill, and a guarded
# call must agree with the unguarded one everywhere the caller reads.
# ---------------------------------------------------------------------------

def test_fit_guard_skips_dead_blocks_bit_identical(monkeypatch):
    """lasso_fit with an active mask whose trailing blocks are all dead
    (the post-compaction layout): guarded output equals unguarded on
    every lane — dead lanes carry all-zero windows, whose computed fit
    IS zero, so the skip fill is exact."""
    from firebird_tpu.ccd import harmonic

    # Narrow blocks keep the two-block interpret run tier-1 cheap.
    monkeypatch.setattr(pallas_ops, "fit_block_p", lambda *a: 128)
    rng = np.random.default_rng(8)
    T, B, K = 40, 7, params.MAX_COEFS
    BP = pallas_ops.fit_block_p(T, B, 2)
    P = 2 * BP                     # two blocks; block 1 fully dead
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], K), jnp.float32)
    Yt = jnp.asarray(rng.integers(0, 5000, (B, T, P)), jnp.int16)
    active = np.zeros(P, bool)
    active[: BP // 2] = True       # dense prefix, as compaction leaves it
    w = jnp.asarray(
        (rng.random((P, T)) < 0.8) & active[:, None], jnp.float32)
    mask = jnp.ones((P, K), bool)
    ref = pallas_ops.lasso_fit(Yt, w, X, mask, interpret=True)
    got = pallas_ops.lasso_fit(Yt, w, X, mask,
                               active=jnp.asarray(active), interpret=True)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # the skipped block really wrote zeros
    assert (np.asarray(got[0])[BP:] == 0).all()


def test_guarded_fit_inside_shard_map(monkeypatch):
    """The guarded kernels' per-block count operand composes with
    shard_map the same way the kernels themselves do (each shard runs
    its own single-device Mosaic call — the cnt-ref BlockSpec included):
    chip-sharded guarded lasso_fit equals the per-chip direct calls, and
    an all-dead shard still writes zeros through its guard."""
    from jax.sharding import PartitionSpec

    from firebird_tpu.ccd import harmonic
    from firebird_tpu.parallel import make_mesh

    monkeypatch.setattr(pallas_ops, "fit_block_p", lambda *a: 128)
    rng = np.random.default_rng(10)
    T, B, K = 40, 7, params.MAX_COEFS
    BP = pallas_ops.fit_block_p(T, B, 2)
    P, D = 2 * BP, 2               # two blocks per chip, two shards
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], K), jnp.float32)
    Yt = jnp.asarray(rng.integers(0, 5000, (D, B, T, P)), jnp.int16)
    active = np.zeros((D, P), bool)
    active[0, : BP // 2] = True    # shard 0: dense prefix
    w = jnp.asarray((rng.random((D, P, T)) < 0.8) & active[..., None],
                    jnp.float32)
    mask = jnp.ones((D, P, K), bool)
    act = jnp.asarray(active)

    mesh = make_mesh(n_devices=D)
    spec = PartitionSpec("data")

    def local(Ytc, wc, mc, ac):
        out = pallas_ops.lasso_fit(Ytc[0], wc[0], X, mc[0], active=ac[0],
                                   interpret=True)
        return jax.tree_util.tree_map(lambda o: o[None], out)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 4,
                       out_specs=spec, check_vma=False)
    got = fn(Yt, w, mask, act)
    for d in range(D):
        ref = pallas_ops.lasso_fit(Yt[d], w[d], X, mask[d],
                                   active=act[d], interpret=True)
        for r, g in zip(ref, (got[0][d], got[1][d])):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # shard 1 has no active lane: every block skipped, zeros written
    assert (np.asarray(got[0])[1] == 0).all()


def test_monitor_scored_guard_matches_on_active_lanes():
    """monitor_chain_scored under a dense-prefix in_mon mask: guarded ==
    unguarded on every lane the caller uses (in_mon lanes; the rest are
    masked downstream, kernel._mon_block)."""
    from firebird_tpu.ccd import harmonic
    from firebird_tpu.ccd.sensor import chi2_thresholds

    rng = np.random.default_rng(9)
    T, nb, K = 48, 5, params.MAX_COEFS
    BP = pallas_ops.scored_block_p(T, nb, 2)
    P = 2 * BP
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], K), jnp.float32)
    Yd = jnp.asarray(rng.integers(0, 5000, (nb, T, P)), jnp.int16)
    coefs = jnp.asarray(rng.normal(0, 1, (P, nb, K)), jnp.float32)
    dden = jnp.asarray(rng.uniform(50, 200, (P, nb)), jnp.float32)
    alive = jnp.asarray(rng.random((P, T)) < 0.8)
    included = jnp.asarray(rng.random((P, T)) < 0.3)
    cur_k = jnp.asarray(rng.integers(0, T // 2, P), jnp.int32)
    nlast = jnp.asarray(rng.integers(12, 40, P), jnp.int32)
    in_mon = jnp.asarray(np.arange(P) < BP // 3)   # dense prefix
    ct, ot = chi2_thresholds(nb)
    kw = dict(change_thr=float(ct), outlier_thr=float(ot), interpret=True)
    ref = pallas_ops.monitor_chain_scored(Yd, coefs, dden, X, alive,
                                          included, cur_k, nlast, in_mon,
                                          **kw)
    got = pallas_ops.monitor_chain_scored(Yd, coefs, dden, X, alive,
                                          included, cur_k, nlast, in_mon,
                                          active=in_mon, **kw)
    use = np.asarray(in_mon)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k])[use],
                                      np.asarray(got[k])[use], err_msg=k)
        # the dead trailing block wrote zeros/False
        assert (np.asarray(got[k])[BP:] == 0).all(), k


@pytest.mark.slow  # ~35s: two interpret-mode traces of the W-unrolled
# init body; the tier-1 guard coverage stays with the fit/monitor/cd
# rungs, which exercise the same _when_active plumbing.
def test_init_window_guard_passes_alive_through(monkeypatch):
    """init_window's skipped blocks mirror kernel._init_zeros: flags and
    indices zero, alive passed through untouched."""
    from firebird_tpu.ccd import harmonic
    from firebird_tpu.ccd.sensor import LANDSAT_ARD

    # Narrow blocks + a small W keep the two-block interpret run tier-1
    # cheap: the init body unrolls per window slot, and interpret-mode
    # cost is dominated by tracing that body, not by lanes.
    monkeypatch.setattr(pallas_ops, "init_block_p", lambda *a: 128)
    rng = np.random.default_rng(10)
    T, B, K, NT, W = 32, 7, params.MAX_COEFS, params.TMASK_COEFS, 8
    BP = pallas_ops.init_block_p(T, W, B, 2)
    P = 2 * BP
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], K), jnp.float32)
    Xt_full = harmonic.design_matrix(t, t[0], params.TMASK_COEFS + 1)
    Xt = jnp.asarray(np.concatenate([Xt_full[:, :1], Xt_full[:, 2:]], 1),
                     jnp.float32)
    Yt = jnp.asarray(rng.integers(0, 5000, (B, T, P)), jnp.int16)
    vario = jnp.asarray(rng.uniform(20, 100, (P, B)), jnp.float32)
    alive = jnp.asarray(rng.random((P, T)) < 0.7)
    cur_i = jnp.asarray(rng.integers(0, T // 2, P), jnp.int32)
    in_init = jnp.asarray(np.arange(P) < BP // 2)  # block 1 fully dead
    kw = dict(W=W, sensor=LANDSAT_ARD, interpret=True)
    ref = pallas_ops.init_window(alive, cur_i, in_init,
                                 jnp.asarray(t, jnp.float32), X, Xt, Yt,
                                 vario, **kw)
    got = pallas_ops.init_window(alive, cur_i, in_init,
                                 jnp.asarray(t, jnp.float32), X, Xt, Yt,
                                 vario, active=in_init, **kw)
    use = np.asarray(in_init)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k])[use],
                                      np.asarray(got[k])[use], err_msg=k)
    # the skipped block passes alive through (Tmask removes nothing for
    # non-INIT lanes) and zeroes the flags
    np.testing.assert_array_equal(np.asarray(got["alive_init"])[BP:],
                                  np.asarray(alive)[BP:])
    assert not np.asarray(got["init_ok"])[BP:].any()


def test_lasso_cd_and_tmask_guards():
    """The remaining guarded kernels: all-dead calls fill exact zeros;
    mixed calls agree with unguarded on active lanes."""
    G, c, d, m = _systems(P=24, dtype=jnp.float64)
    dead = jnp.zeros(24, bool)
    z = pallas_ops.lasso_cd(G, jnp.zeros_like(c), d, m, active=dead,
                            interpret=True)
    assert (np.asarray(z) == 0).all()
    act = jnp.asarray(np.arange(24) < 9)
    ref = pallas_ops.lasso_cd(G, c, d, m, interpret=True)
    got = pallas_ops.lasso_cd(G, c, d, m, active=act, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref)[:9], np.asarray(got)[:9])

    rng = np.random.default_rng(12)
    P, W, nt, nb = 20, 16, params.TMASK_COEFS, 2
    Xtw = jnp.asarray(rng.normal(0, 1, (P, W, nt)), jnp.float32)
    Y2 = jnp.asarray(rng.normal(1000, 200, (P, nb, W)), jnp.float32)
    w = jnp.asarray(rng.random((P, W)) < 0.8, jnp.float32)
    v2 = jnp.asarray(rng.uniform(20, 80, (P, nb)), jnp.float32)
    ref = pallas_ops.tmask_bad(Xtw, Y2, w, v2, interpret=True)
    act = jnp.ones(P, bool)
    got = pallas_ops.tmask_bad(Xtw, Y2, w, v2, active=act, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    z = pallas_ops.tmask_bad(Xtw, Y2, w, v2, active=jnp.zeros(P, bool),
                             interpret=True)
    assert not np.asarray(z).any()


def _trace_wire(T, dtype, P=16):
    """Trace (not run) the wire-dtype batch program at [1, 7, P, T]."""
    from firebird_tpu.driver import core

    shapes = ((1, T), (1,), (1, 7, P, T), (1, P, T))
    av = tuple(jax.ShapeDtypeStruct(s, d)
               for s, d in zip(shapes, core.wire_avatar_dtypes()))
    jax.eval_shape(lambda *a: kernel._detect_batch_wire(
        *a, dtype=jnp.dtype(dtype), wcap=48, sensor=kernel.LANDSAT_ARD,
        max_segments=kernel.MAX_SEGMENTS), *av)


def _refused_traced(T, dtype):
    """(counter increase, warning lines) from tracing one program."""
    import logging

    from firebird_tpu.obs import metrics as obs_metrics

    def count():
        return obs_metrics.get_registry().snapshot()["counters"].get(
            "kernel_pallas_refused", 0)

    lines = []
    h = logging.Handler(logging.WARNING)
    h.emit = lambda r: lines.append(r.getMessage())
    log = logging.getLogger("firebird.pyccd")
    log.addHandler(h)
    before = count()
    try:
        _trace_wire(T, dtype)
    finally:
        log.removeHandler(h)
    return count() - before, "\n".join(lines)


def test_mega_refused_by_vmem_guard_is_logged_and_counted(monkeypatch):
    """A mega request the VMEM guard refuses (T far past the full-archive
    bucket) falls back to the XLA loop OUT LOUD: one log line and the
    kernel_pallas_refused counter per traced program."""
    assert not pallas_ops.mega_fits(2048, 48, 7, kernel.MAX_SEGMENTS, 2)
    monkeypatch.setenv("FIREBIRD_PALLAS", "mega")
    n, log = _refused_traced(2048, jnp.float32)
    assert n == 1
    assert "'mega' requested but refused (mega_fits" in log


def test_pallas_request_refused_for_float64_on_tpu(monkeypatch):
    """Mosaic lowers float32 only: a float64 program traced for a TPU
    refuses each requested route by name instead of dropping it silently
    (the backend is faked here; only the tracing decision is under test)."""
    monkeypatch.setenv("FIREBIRD_PALLAS", "fit,score")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, log = _refused_traced(64, jnp.float64)
    assert n == 2
    assert "'fit' requested but refused (Mosaic lowers float32 only" in log
    assert "'score' requested but refused" in log
