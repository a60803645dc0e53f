"""The compulsory-work count behind ``kernel.detect_roofline`` and the
peak table it is held against."""

import importlib.util
import os

import pytest

from benchmark import work

ROOT = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
V5E = "TPU v5 lite"


def roofline_reader():
    path = os.path.join(ROOT, "benchmark", "metrics",
                        "kernel.detect_roofline.py")
    spec = importlib.util.spec_from_file_location("roofline_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_landsat_chip_by_hand():
    P, n, B, D = 10_000, 1146, 7, 5
    w = work.compulsory(P, n, B, D)
    read = P * n * (2 * 7 + 1) + 4 * n
    written = P * n / 8 + P * 4 * (5 + 7 * 10)
    assert w["bytes"] == pytest.approx(read + written)
    assert w["ops"] == P * n * D * (2 * 8 + 3)


def test_peaks_table():
    p = work.peaks(V5E)
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v4")


def test_memory_floor_binds_for_landsat():
    lt = work.least_time(work.compulsory(10_000, 1146, 7, 5),
                         work.peaks(V5E))
    assert lt["bound"] == "memory"
    assert lt["seconds"] == pytest.approx(lt["memory_s"])
    assert lt["compute_s"] < lt["memory_s"]


def test_compute_floor_binds_when_operations_dominate():
    peak = dict(work.peaks(V5E), hbm_bytes_per_s=1e18)
    assert work.least_time(work.compulsory(100, 100, 7, 5),
                           peak)["bound"] == "compute"


ROUTES = [
    {},
    {"FIREBIRD_PALLAS": "1"},
    {"FIREBIRD_PALLAS": "lasso,fit"},
    {"FIREBIRD_FUSED_FIT": "1"},
    {"FIREBIRD_FUSED_FIT": "mon"},
    {"FIREBIRD_MIXED_PRECISION": "1"},
    {"FIREBIRD_PALLAS": "1", "FIREBIRD_FUSED_FIT": "1",
     "FIREBIRD_MIXED_PRECISION": "1"},
    {"FIREBIRD_COMPACT": "0"},
]


@pytest.mark.parametrize("env", ROUTES, ids=lambda e: ",".join(
    f"{k}={v}" for k, v in e.items()) or "default")
def test_count_is_the_same_whatever_route_runs(env, monkeypatch):
    """The count and the roofline share read from it depend on the data's
    shapes alone: every route switch of the program leaves them as they
    are (the program's own per-round byte model does not; see
    ``ccd/flops.round_bytes``)."""
    base_w = work.compulsory(10_000, 1146, 7, 5)
    ctx = dict(kernel_s=4.6, peak=work.peaks(V5E), pixels=10_000,
               acquisitions=1146, bands=7, detection_bands=5, chips=4)
    base_share = roofline_reader().read(ctx)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert work.compulsory(10_000, 1146, 7, 5) == base_w
    assert roofline_reader().read(ctx) == base_share


def test_roofline_share_is_least_time_over_kernel_time():
    ctx = dict(peak=work.peaks(V5E), pixels=10_000, acquisitions=1146,
               bands=7, detection_bands=5, chips=4)
    least = work.least_time(work.compulsory(10_000, 1146, 7, 5),
                            ctx["peak"])["seconds"] * 4
    read = roofline_reader().read
    assert read(dict(ctx, kernel_s=least)) == pytest.approx(100.0)
    assert read(dict(ctx, kernel_s=10 * least)) == pytest.approx(10.0)
    assert read(dict(ctx, kernel_s=None)) is None
    assert read(dict(ctx, kernel_s=least, peak=None)) is None
