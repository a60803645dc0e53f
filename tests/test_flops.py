"""The closed-form FLOP/roofline model (ccd/flops.py): internal
consistency, scaling laws, and a cross-check of the dominant term against
XLA's own cost analysis of the same algebra."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firebird_tpu.ccd import flops, params
from firebird_tpu.ccd.sensor import LANDSAT_ARD, SENTINEL2


def test_round_flops_scaling():
    base = flops.round_flops(1000, 400, 120)["total"]
    # linear in P
    assert np.isclose(flops.round_flops(2000, 400, 120)["total"], 2 * base,
                      rtol=1e-6)
    # monotone in T and W
    assert flops.round_flops(1000, 800, 120)["total"] > base
    assert flops.round_flops(1000, 400, 240)["total"] > base
    # every group positive
    assert all(v > 0 for v in flops.round_flops(1000, 400, 120).values())


def test_detect_flops_composition():
    r = flops.round_flops(500, 300, 100)["total"]
    d = flops.detect_flops(500, 300, 100, rounds=20)
    assert d["total"] == r * 20 + flops.setup_flops(500, 300)
    assert np.isclose(d["per_pixel"], d["total"] / 500)


def test_sentinel2_costs_more_per_obs():
    """12 bands cost more arithmetic than 7 at the same shape."""
    l = flops.round_flops(1000, 400, 120, LANDSAT_ARD)["total"]
    s = flops.round_flops(1000, 400, 120, SENTINEL2)["total"]
    assert s > l


def test_gram_corr_term_matches_xla_cost_analysis():
    """The model's Lasso Gram+corr term (the dominant per-round matmuls,
    kernel.py:174-175) agrees with XLA's flop count for the same algebra
    to within fusion/bookkeeping noise."""
    P, T, B, K = 256, 128, 7, params.MAX_COEFS
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    XX = (X[:, :, None] * X[:, None, :]).reshape(T, K * K)
    Y = jnp.asarray(rng.normal(size=(P, B, T)), jnp.float32)
    w = jnp.asarray((rng.uniform(size=(P, T)) > 0.5), jnp.float32)

    def gram_corr(w, XX, Y, X):
        G = (w @ XX).reshape(-1, K, K)
        c = jnp.einsum("pbt,tc->pbc", Y * w[:, None, :], X)
        return G, c

    analysis = jax.jit(gram_corr).lower(w, XX, Y, X).compile().cost_analysis()
    xla_flops = analysis["flops"] if isinstance(analysis, dict) \
        else analysis[0]["flops"]
    model = 2.0 * P * T * K * K + 2.0 * P * B * T * K + P * B * T
    assert 0.5 * model <= xla_flops <= 1.5 * model, (xla_flops, model)


def test_peak_lookup():
    pk = flops.peak_for("TPU v5 lite")
    assert pk is not None and pk.bf16_flops == 197e12
    assert flops.peak_for("cpu") is None
    assert flops.peak_for("TPU v4") is not None


def test_bench_detail_shapes():
    d = flops.bench_detail(pixels_per_sec=1e4, P=80000, T=480, W=160,
                           S=8, rounds=40.0, device_kind="TPU v5 lite")
    for key in ("model_flops_per_pixel", "arithmetic_intensity",
                "achieved_tflops", "mfu_pct_vs_f32_peak",
                "compute_bound_pixels_per_sec", "hbm_bound_pixels_per_sec"):
        assert key in d and d[key] > 0, key
    # no peak entry for CPU: MFU keys absent, model keys still present
    c = flops.bench_detail(pixels_per_sec=100.0, P=80000, T=480, W=160,
                           S=8, rounds=40.0, device_kind="cpu")
    assert "mfu_pct_vs_f32_peak" not in c
    assert c["model_flops_per_pixel"] == d["model_flops_per_pixel"]




# The byte and flop counts of the default route and of the Pallas
# components, pinned at the values the models gave while the routes that
# never won on a chip still had terms in them: deleting those terms must
# not move these.  (P, T, W, S, rounds, phase rounds) per sensor.
_SHAPES = {"landsat": (LANDSAT_ARD, 10_000, 1152, 48, 10, 40.0,
                       (12.0, 20.0, 6.0)),
           "sentinel2": (SENTINEL2, 90_000, 448, 80, 10, 30.0, None)}
_PINNED = {
    ("landsat", ""): (147677440000.0, 499845493728.0),
    ("landsat", "monitor,score,tmask"): (129245440000.0, 499845493728.0),
    ("sentinel2", ""): (1812240000000.0, 7580893348672.0),
    ("sentinel2", "monitor,score,tmask"): (1763856000000.0,
                                           7580893348672.0),
}


@pytest.mark.parametrize("shape,route", sorted(_PINNED))
def test_route_counts_are_pinned(shape, route):
    sensor, P, T, W, S, rounds, phase = _SHAPES[shape]
    pallas = tuple(route.split(",")) if route else ()
    by = flops.round_bytes(P, T, W, S, 4, sensor, rounds=rounds,
                           phase_rounds=phase, pallas=pallas)
    fl = flops.detect_flops(P, T, W, rounds, sensor, phase_rounds=phase)
    assert (by, fl["total"]) == _PINNED[(shape, route)]
