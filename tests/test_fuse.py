"""The cross-device rebalancing ring (FIREBIRD_REBALANCE, parallel.mesh):
it must migrate straggler lanes between devices of a simulated mesh
without moving a single store row, and account the migrated lanes in the
occupancy/metric surface.
"""

import os

import numpy as np
import jax.numpy as jnp

from firebird_tpu.ccd import kernel, params, synthetic
from firebird_tpu.ingest.packer import PackedChips


def _grid():
    return synthetic.acquisition_dates("1995-01-01", "1997-06-01", 16)


def test_rebalance_ring_row_identity_and_migration():
    """The rebalancing ring on a simulated 2-device mesh: a forced-
    ragged workload (all long-lived pixels on one device) must migrate
    lanes (lanes_migrated > 0), keep every store row identical to the
    ring-off dispatch, and land the migrated lanes in the occupancy /
    metric accounting."""
    from firebird_tpu.obs import metrics as obs_metrics
    from firebird_tpu.parallel import make_mesh
    from firebird_tpu.parallel.mesh import detect_sharded

    rng = np.random.default_rng(5)
    t = _grid()
    T = t.shape[0]
    P = 48

    def chip(n_std, brk):
        px = []
        for i in range(n_std):
            Y = synthetic.harmonic_series(t, rng)
            if brk and i % 2 == 0:
                Y[:, T // 2:] += 800.0
            px.append((Y, np.full(T, synthetic.QA_CLEAR, np.uint16)))
        while len(px) < P:
            px.append((np.full((7, T), params.FILL_VALUE, np.float64),
                       np.full(T, synthetic.QA_FILL, np.uint16)))
        return px

    busy, idle = chip(16, True), chip(2, False)
    Ys, Qs = [], []
    for px in (busy, idle):
        Y, q = zip(*px)
        Ys.append(np.stack([np.asarray(y, np.int16)
                            for y in Y]).transpose(1, 0, 2))
        Qs.append(np.stack(q))
    p = PackedChips(cids=np.stack([np.zeros(2, np.int64),
                                   np.ones(2, np.int64)]),
                    dates=np.stack([t, t]).astype(np.int32),
                    spectra=np.stack(Ys), qas=np.stack(Qs),
                    n_obs=np.array([T, T], np.int32))

    mesh = make_mesh(n_devices=2)
    old = {k: os.environ.get(k)
           for k in ("FIREBIRD_REBALANCE", "FIREBIRD_REBALANCE_THRESHOLD",
                     "FIREBIRD_COMPACT_MIN_LANES", "FIREBIRD_PALLAS")}
    try:
        # The ring lives at the stage-2 boundary: lower the cascade gate
        # so the P=48 shape builds it (trace-time read).  The ring is
        # orthogonal to WHICH kernel computes the lanes (it migrates
        # state, not programs), so this test runs the cheap lax path;
        # tracing two interpret-Pallas cascade programs here would
        # double the module's tier-1 cost for no coverage.
        os.environ["FIREBIRD_COMPACT_MIN_LANES"] = "8"
        os.environ["FIREBIRD_PALLAS"] = "0"
        os.environ["FIREBIRD_REBALANCE"] = "0"
        off = detect_sharded(p, mesh, dtype=jnp.float32, compact=True)
        os.environ["FIREBIRD_REBALANCE"] = "1"
        os.environ["FIREBIRD_REBALANCE_THRESHOLD"] = "0.1"
        on = detect_sharded(p, mesh, dtype=jnp.float32, compact=True)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    for f in ("n_segments", "seg_meta", "seg_rmse", "seg_mag", "seg_coef",
              "mask", "procedure"):
        np.testing.assert_array_equal(np.asarray(getattr(on, f)),
                                      np.asarray(getattr(off, f)),
                                      err_msg=f)
    assert off.lanes_migrated is None       # ring off -> field absent
    lm = np.asarray(on.lanes_migrated)
    assert lm.shape == (2,) and lm.sum() > 0
    # migrated-lane accounting: the occupancy capture still covers every
    # executed round, and record_occupancy lands the migration counters.
    obs_metrics.reset_registry()
    det = kernel.record_occupancy(on)
    assert det is not None and det["lanes_migrated"] == int(lm.sum())
    counters = obs_metrics.get_registry().snapshot()["counters"]
    assert counters["kernel_lanes_migrated"] == int(lm.sum())
    assert counters["rebalance_migrations"] == 1


def test_rebalance_spec_resolution(monkeypatch):
    """Knob resolution + cache-key hygiene: off / single-device meshes
    resolve to None; the spec is hashable (it rides the
    sharded_detect_fn lru_cache key) and carries the env threshold."""
    from firebird_tpu.parallel import make_mesh
    from firebird_tpu.parallel.mesh import RebalanceSpec, rebalance_spec

    mesh2 = make_mesh(n_devices=2)
    monkeypatch.delenv("FIREBIRD_REBALANCE", raising=False)
    assert rebalance_spec(mesh2) is None
    monkeypatch.setenv("FIREBIRD_REBALANCE", "1")
    monkeypatch.setenv("FIREBIRD_REBALANCE_THRESHOLD", "0.5")
    spec = rebalance_spec(mesh2)
    assert isinstance(spec, RebalanceSpec)
    assert spec.n == 2 and spec.threshold == 0.5 and spec.axis == "data"
    assert hash(spec) == hash(RebalanceSpec(axis="data", n=2,
                                            threshold=0.5, rdma=False))
    mesh1 = make_mesh(n_devices=1)
    assert rebalance_spec(mesh1) is None


