"""The Pallas components (monitor, score, tmask) against their XLA twins
(interpret mode on CPU; the same kernels run compiled on TPU under
FIREBIRD_PALLAS), FIREBIRD_PALLAS parsing, and the XLA Lasso fit the
event loop runs against a float64 numpy reference."""

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


# The XLA CD loop against the float64 numpy loop: the two reduce over k
# in different association orders, so they differ at machine epsilon per
# update; 50 iterations of soft-thresholding amplify that slightly in
# f32.  Tolerances mirror the kernel-vs-oracle parity ladder
# (test_ccd_reference).
_TOL = {jnp.dtype(jnp.float32): dict(rtol=1e-2, atol=1e-2),
        jnp.dtype(jnp.float64): dict(rtol=1e-8, atol=1e-8)}


def _lasso_cd_numpy(G, c, mask):
    """harmonic.lasso_cd_gram per pixel and band in float64 over the
    coordinates ``mask`` allows (a prefix); the others stay zero, as the
    kernel's coefmask keeps them."""
    G, c, mask = (np.asarray(a, np.float64) for a in (G, c, mask))
    out = np.zeros(c.shape)
    for p in range(c.shape[0]):
        k = int(mask[p].sum())
        for b in range(c.shape[1]):
            out[p, b, :k] = harmonic.lasso_cd_gram(G[p, :k, :k],
                                                   c[p, b, :k])
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_lasso_cd_lax_matches_numpy_reference(dtype):
    """The event loop's CD loop (kernel._lasso_cd_lax) reproduces
    harmonic.lasso_cd_gram, the float64 reference's update, on
    realistic Gram systems with 4/6/8-coefficient masks."""
    G, c, diag, mask = _systems(dtype=dtype)
    got = kernel._lasso_cd_lax(G, c, diag, mask)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               _lasso_cd_numpy(G, c, mask),
                               **_TOL[jnp.dtype(dtype)])


def test_lasso_cd_lax_under_vmap():
    """The detect path runs the CD loop under vmap over chips."""
    Gs, cs, ds, ms = zip(*[_systems(P=16, dtype=jnp.float64, seed=s)
                           for s in range(3)])
    G, c, d, m = (jnp.stack(x) for x in (Gs, cs, ds, ms))
    got = jax.vmap(kernel._lasso_cd_lax)(G, c, d, m)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(got[i]),
                                   _lasso_cd_numpy(G[i], c[i], m[i]),
                                   **_TOL[jnp.dtype(jnp.float64)])


@pytest.mark.slow  # ~30-60s interpret-mode run; tier-1 (-m 'not slow') budget keeps the faster per-kernel parity rungs instead
def test_pallas_flag_routes_full_detect(monkeypatch):
    """FIREBIRD_PALLAS=1 routes the whole chip detector through the Pallas
    kernels with results matching the default path."""
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
    the Pallas kernels: each shard runs its own single-device Mosaic
    call, so no SPMD partitioning rule is needed."""
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
    """FIREBIRD_PALLAS=1 routes the monitor chain (and the other
    components) through Pallas; full-detect results must equal the
    default path."""
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


# A distinct wcap offset per case: the component list is read at trace
# time and the jit cache keys on static arguments only.
_DETECT_ROUTES = {"monitor,score,tmask": 24, "monitor": 48, "score": 64,
                  "tmask": 72}


@pytest.mark.parametrize("route", sorted(_DETECT_ROUTES))
def test_full_pallas_detect_matches_default(route, monkeypatch):
    """FIREBIRD_PALLAS routes each kept component, and all three
    together, through Pallas; full-detect results must equal the default
    path."""
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    src = SyntheticSource(seed=44, start="1995-01-01", end="1999-01-01",
                          cloud_frac=0.2)
    p = pack([src.chip(100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :64, :], qas=p.qas[:, :64, :],
                    n_obs=p.n_obs, sensor=p.sensor)
    ref = kernel.detect_packed(p, dtype=jnp.float32)
    monkeypatch.setenv("FIREBIRD_PALLAS", route)
    off = _DETECT_ROUTES[route]
    monkeypatch.setattr(kernel, "window_cap",
                        lambda pk, _orig=kernel.window_cap: _orig(pk) + off)
    got = kernel.detect_packed(p, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.n_segments),
                                  np.asarray(ref.n_segments))
    np.testing.assert_allclose(np.asarray(got.seg_meta),
                               np.asarray(ref.seg_meta), atol=1e-5)


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
    for env, score, monitor, tmask in [
            ("0", False, False, False), ("", False, False, False),
            ("1", True, True, True),
            ("score", True, False, False),
            ("monitor,tmask", False, True, True),
            (" score , tmask ", True, False, True)]:
        monkeypatch.setenv("FIREBIRD_PALLAS", env)
        comps = kernel.pallas_components()
        assert ("score" in comps) is score, env
        assert ("monitor" in comps) is monitor, env
        assert ("tmask" in comps) is tmask, env


@pytest.mark.parametrize("name", ["lasso", "fit", "init", "mega",
                                  "moniter"])
def test_pallas_refuses_unknown_component(name, monkeypatch):
    """A name that is no component (the deleted routes, a misspelling)
    raises, naming the valid components, instead of running the XLA
    loop in silence."""
    monkeypatch.setenv("FIREBIRD_PALLAS", f"score,{name}")
    with pytest.raises(ValueError) as e:
        kernel.pallas_components()
    assert repr(name) in str(e.value)
    assert "monitor, score, tmask" in str(e.value)


def test_pallas_one_means_the_three_components(monkeypatch):
    monkeypatch.setenv("FIREBIRD_PALLAS", "1")
    assert kernel.pallas_components() == {"monitor", "score", "tmask"}
    assert set(kernel.PALLAS_COMPONENTS) == {"monitor", "score", "tmask"}


# ---------------------------------------------------------------------------
# Per-block skip guards (active-lane compaction, ISSUE 6): a block with
# no active lane must cost only its predicate + zero-fill, and a guarded
# call must agree with the unguarded one everywhere the caller reads.
# ---------------------------------------------------------------------------

def _fit_systems(rng, C, P, T, dtype):
    """Designs, spectra, dense-prefix active lanes (a chip with none),
    their fit windows and coefficient masks, as the event loop builds
    them: lanes outside ``active`` carry all-zero windows."""
    K = params.MAX_COEFS
    t = np.sort(rng.integers(729000, 730500, T)).astype(np.float64)
    X = jnp.asarray(harmonic.design_matrix(t, t[0], K), dtype)
    XX = (X[:, :, None] * X[:, None, :]).reshape(T, -1)
    Y = jnp.asarray(rng.integers(0, 5000, (C, P, 7, T)), dtype)
    active = np.zeros((C, P), bool)
    active[0, : P // 3] = True                 # chip 0: dense prefix
    w = jnp.asarray((rng.random((C, P, T)) < 0.8) & active[..., None],
                    dtype)
    nc = rng.choice([4, 6, 8], (C, P))
    mask = jnp.asarray(np.arange(K) < nc[..., None])
    return X, XX, Y, jnp.asarray(active), w, mask


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fit_guard_skips_dead_blocks_bit_identical(dtype):
    """kernel._fit_chip under its ``active`` guard equals the unguarded
    fit bit for bit on every lane: dead lanes carry all-zero windows,
    whose fit IS zero, and an all-dead call (the skip branch) writes
    those same zeros."""
    X, XX, Y, active, w, mask = _fit_systems(np.random.default_rng(8),
                                             2, 40, 40, dtype)
    for ch in range(2):
        res = dict(X=X, Y=Y[ch], XX=XX)
        ref = kernel._fit_chip(res, w[ch], mask[ch])
        got = kernel._fit_chip(res, w[ch], mask[ch], active=active[ch])
        for r, g in zip(ref, got):
            assert g.dtype == dtype
            np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
            assert (np.asarray(g)[~np.asarray(active[ch])] == 0).all()
    coefs = kernel._fit_chip(dict(X=X, Y=Y[1], XX=XX), w[1], mask[1],
                             with_rmse=False, active=active[1])
    assert not np.asarray(coefs).any()


def test_guarded_fit_inside_shard_map():
    """The guarded fit composes with shard_map as the event loop runs it
    (chips vmapped inside each shard): on a 1-device mesh it equals the
    unsharded fit bit for bit, and the all-dead chip still writes
    zeros."""
    from jax.sharding import PartitionSpec

    from firebird_tpu.parallel import make_mesh

    X, XX, Y, active, w, mask = _fit_systems(np.random.default_rng(10),
                                             2, 40, 40, jnp.float32)
    fit = jax.vmap(lambda y, ww, m, a: kernel._fit_chip(
        dict(X=X, Y=y, XX=XX), ww, m, active=a))
    spec = PartitionSpec("data")
    sharded = jax.jit(jax.shard_map(fit, mesh=make_mesh(n_devices=1),
                                    in_specs=(spec,) * 4, out_specs=spec,
                                    check_vma=False))
    got = sharded(Y, w, mask, active)
    ref = jax.jit(fit)(Y, w, mask, active)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    assert not np.asarray(got[0])[1].any()



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


def test_monitor_chain_guard_matches_on_active_lanes():
    """monitor_chain under a dense-prefix in_mon mask: guarded ==
    unguarded on every lane the caller uses, and the dead trailing block
    writes zeros (kernel._mon_block masks it downstream)."""
    rng = np.random.default_rng(13)
    T = 48
    BP = pallas_ops.mon_block_p(T)
    P = 2 * BP
    alive = rng.random((P, T)) < 0.8
    s = jnp.asarray(rng.gamma(2.0, 6.0, (P, T)), jnp.float32)
    included = jnp.asarray((rng.random((P, T)) < 0.4) & alive)
    rank = jnp.cumsum(jnp.asarray(alive), -1) - 1
    cur_k = jnp.asarray(rng.integers(0, T, P), jnp.int32)
    nlast = jnp.asarray(rng.integers(1, 40, P), jnp.int32)
    in_mon = jnp.asarray(np.arange(P) < BP // 3)   # dense prefix
    args = (s, jnp.asarray(alive), included, rank, cur_k, nlast, in_mon)
    kw = dict(change_thr=11.07, outlier_thr=15.09, interpret=True)
    ref = pallas_ops.monitor_chain(*args, **kw)
    got = pallas_ops.monitor_chain(*args, active=in_mon, **kw)
    use = np.asarray(in_mon)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k])[use],
                                      np.asarray(got[k])[use], err_msg=k)
        assert (np.asarray(got[k])[BP:] == 0).all(), k


def test_tmask_guards():
    """The guarded tmask kernel: all-dead calls fill exact zeros; a
    guarded call agrees with the unguarded one."""
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


def test_pallas_request_refused_for_float64_on_tpu(monkeypatch):
    """Mosaic lowers float32 only: a float64 program traced for a TPU
    refuses each requested route by name instead of dropping it silently
    (the backend is faked here; only the tracing decision is under test)."""
    monkeypatch.setenv("FIREBIRD_PALLAS", "monitor,score")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, log = _refused_traced(64, jnp.float64)
    assert n == 2
    assert "'monitor' requested but refused (Mosaic lowers float32 only" \
        in log
    assert "'score' requested but refused" in log


def test_unknown_pallas_component_fails_the_trace(monkeypatch):
    """A deployment still naming a deleted route fails when the detect
    program is traced, not later as an XLA run read as the route's."""
    monkeypatch.setenv("FIREBIRD_PALLAS", "fit")
    with pytest.raises(ValueError, match="'fit'"):
        _trace_wire(80, jnp.float32)
