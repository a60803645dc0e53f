"""Wide randomized kernel-vs-oracle parity sweep (the CI fuzz tests'
big brother).

CI runs a fixed handful of fuzz grids (tests/test_fuzz_parity.py); this
tool sweeps hundreds more — random archive spans, cadences, drop/dup
rates, QA mixes, step changes, spikes — and reports structural agreement
between the accelerator kernel and the float64 NumPy oracle on every
pixel.  The numbers cited in docs/ARCHITECTURE.md (§parity audit) come
from runs of this tool.

    python tools/fuzz_sweep.py --seeds 1000:1036            # Landsat
    python tools/fuzz_sweep.py --seeds 3000:3016 --sensor sentinel2-l2a
    python tools/fuzz_sweep.py --seeds 1000:1018 --compare-f32

The docs' published envelope came from: Landsat seeds 1000:1036,
2000:2036, 4000:4036, 6000:6036, 7000:7036 at --pixels 40 (180 grids);
Sentinel-2 seeds 3000:3016, 5000:5016, 8000:8016 at --pixels 32
(48 grids); f32 agreement seeds 1000:1018 at --pixels 40.

Exit status is non-zero if any pixel diverges structurally (procedures,
model counts, masks, break/start/end days, curve QA, observation counts).
Magnitude/rmse are NOT checked here — their measured float64 envelope is
~2.5e-4 relative (coordinate-descent roundoff amplification, see
tests/test_fuzz_parity.py) and the structural fields are the contract.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(__file__), os.pardir,
                                   ".cache", "jax"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

_root = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
# Repo root first so firebird_tpu imports without an installed package
# (run by script path, sys.path[0] is tools/), then tests/ for the
# shared fuzz-grid builders.
sys.path.insert(0, os.path.join(_root, "tests"))
sys.path.insert(0, _root)

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import test_fuzz_parity as F  # noqa: E402
from firebird_tpu.ccd import kernel  # noqa: E402
from firebird_tpu.ccd.reference import detect_sensor  # noqa: E402
from firebird_tpu.ccd.sensor import SENSORS  # noqa: E402

# Grid-parameter distributions: Landsat draws from the full ARD era;
# other sensors (Sentinel-2 launched 2015) draw recent-era spans.
LANDSAT_STARTS = ["1985-01-01", "1990-06-01", "1995-01-01", "2000-01-01",
                  "2005-01-01"]
RECENT_STARTS = ["2016-01-01", "2018-01-01", "2019-06-01"]


def pyccd_oracle():
    """detect_sensor-shaped adapter over the real lcmap-pyccd package, for
    closing docs/DIVERGENCE.md when an environment can install it
    (pip install lcmap-pyccd==2018.03.12.dev-ncompare.b2).  Landsat-only:
    pyccd's ccd.detect takes the 7 fixed band keywords."""
    try:
        import ccd as pyccd  # the lcmap-pyccd package namespace
    except ImportError as e:
        raise SystemExit(
            "--oracle pyccd needs the lcmap-pyccd package installed "
            "(unavailable offline; see docs/DIVERGENCE.md)") from e

    def detect(dates, spectra, qas, sensor):
        bands = dict(zip(("blues", "greens", "reds", "nirs", "swir1s",
                          "swir2s", "thermals"), np.asarray(spectra)))
        out = dict(pyccd.detect(dates=np.asarray(dates),
                                qas=np.asarray(qas), **bands))
        # Normalize to the reference result contract (reference.py:404-421):
        # pyccd reports its procedure *function* name (e.g.
        # "standard_procedure"); models may be attr-style records.
        proc = str(out.get("procedure", ""))
        for name in ("standard", "permanent-snow", "insufficient-clear"):
            if name.replace("-", "_") in proc.replace("-", "_"):
                out["procedure"] = name
                break
        out["change_models"] = [
            m if isinstance(m, dict)
            else getattr(m, "_asdict", lambda: dict(m))()
            for m in out.get("change_models", [])]
        return out

    return detect


def run_grid(seed: int, sensor, n_pixels: int,
             compare_f32: bool, oracle=detect_sensor,
             mode_diff: bool = False) -> int | None:
    """One grid's divergence count, or None when the grid is skipped
    (fewer than 4 surviving dates).

    ``mode_diff=True`` replaces the oracle assert with a plain-vs-
    adjusted variogram decision diff of the KERNEL (docs/DIVERGENCE.md
    #1): both modes run over the same pixels and the count of pixels
    whose structural record changes is reported (a size-of-surface
    measurement, not a failure)."""
    landsat = sensor.name == "landsat-ard"
    starts = LANDSAT_STARTS if landsat else RECENT_STARTS
    r = np.random.default_rng(seed)
    start = starts[int(r.integers(0, len(starts)))]
    years = int(r.integers(2, 16) if landsat else r.integers(2, 6))
    cad = int(r.choice([8, 12, 16, 24, 32] if landsat else [5, 10, 16]))
    drop = float(r.uniform(0.0, 0.6 if landsat else 0.5))
    dup = float(r.uniform(0.0, 0.15 if landsat else 0.1))
    # A fresh generator with the same seed deliberately replays the stream
    # that chose the grid parameters — a historical quirk kept so the
    # sweeps behind the docs' published numbers regenerate exactly; the
    # grid-shape/pixel-noise correlation it introduces narrows the fuzz
    # space only marginally (every seed still varies both).
    rng = np.random.default_rng(seed)
    t = F._dates(start, f"{int(start[:4]) + years}-01-01", cad, drop, dup,
                 rng)
    if t.shape[0] < 4:
        print(f"SKIPPED seed={seed}: only {t.shape[0]} dates survive",
              flush=True)
        return None
    pixels = [F._fuzz_pixel(t, rng, special=F.SPECIALS.get(i), sensor=sensor)
              for i in range(n_pixels)]
    p = F._pack_pixels(t, [Y for Y, _ in pixels], [q for _, q in pixels],
                       sensor=sensor)
    if mode_diff:
        recs = {}
        for mode in ("plain", "adjusted"):
            os.environ["FIREBIRD_VARIOGRAM"] = mode
            jax.clear_caches()          # the mode is read at trace time
            s = F._unwrap_chip(kernel.detect_packed(p, dtype=jnp.float64))
            d = p.dates[0][: int(p.n_obs[0])]
            recs[mode] = [kernel.segments_to_records(s, d, i, sensor=sensor)
                          for i in range(n_pixels)]
        os.environ.pop("FIREBIRD_VARIOGRAM", None)
        jax.clear_caches()
        diffs = 0
        for i in range(n_pixels):
            a, b = recs["plain"][i], recs["adjusted"][i]
            am, bm = a["change_models"], b["change_models"]
            if (len(am) != len(bm)
                    or a["processing_mask"] != b["processing_mask"]
                    or any(x["break_day"] != y["break_day"]
                           or x["start_day"] != y["start_day"]
                           or x["end_day"] != y["end_day"]
                           for x, y in zip(am, bm))):
                diffs += 1
        print(f"grid seed={seed} T={p.dates.shape[1]} mode-diff "
              f"{diffs}/{n_pixels} pixels", flush=True)
        return diffs
    seg = F._unwrap_chip(kernel.detect_packed(p, dtype=jnp.float64))
    s32 = (F._unwrap_chip(kernel.detect_packed(p, dtype=jnp.float32))
           if compare_f32 else None)
    dates = p.dates[0][: int(p.n_obs[0])]
    T = dates.shape[0]
    bad = 0
    for i in range(n_pixels):
        o = oracle(dates, np.asarray(p.spectra[0, :, i, :T], np.float64),
                   p.qas[0, i, :T], sensor)
        k = kernel.segments_to_records(seg, dates, i, sensor=sensor)
        try:
            F._assert_structural(o, k, i)
        except AssertionError as e:
            bad += 1
            print(f"DIVERGENCE seed={seed} T={T} pixel={i}: {e}", flush=True)
        if s32 is not None:
            k32 = kernel.segments_to_records(s32, dates, i, sensor=sensor)
            a, b = k["change_models"], k32["change_models"]
            if (len(a) != len(b)
                    or any(x["break_day"] != y["break_day"]
                           or x["start_day"] != y["start_day"]
                           or x["end_day"] != y["end_day"]
                           for x, y in zip(a, b))):
                bad += 1
                print(f"F32-DIVERGENCE seed={seed} T={T} pixel={i}",
                      flush=True)
    print(f"grid seed={seed} T={T} done ({bad} divergences)", flush=True)
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", default="1000:1036",
                    help="seed range lo:hi (one grid per seed)")
    ap.add_argument("--sensor", default="landsat-ard",
                    choices=sorted(SENSORS))
    ap.add_argument("--pixels", type=int, default=40,
                    help="adversarial pixels per grid")
    ap.add_argument("--compare-f32", action="store_true",
                    help="also require f32/f64 break-date agreement")
    ap.add_argument("--oracle", default="reference",
                    choices=("reference", "pyccd"),
                    help="reference: in-tree float64 oracle; pyccd: the "
                         "real lcmap-pyccd package (docs/DIVERGENCE.md)")
    ap.add_argument("--variogram", default="adjusted",
                    choices=("plain", "adjusted"),
                    help="variogram rule for BOTH kernel and oracle "
                         "(docs/DIVERGENCE.md #1; default matches the "
                         "production default, params."
                         "variogram_adjusted_default)")
    ap.add_argument("--mode-diff", action="store_true",
                    help="no oracle: diff the kernel's plain vs adjusted "
                         "variogram decisions and count changed pixels")
    args = ap.parse_args()
    lo, hi = (int(v) for v in args.seeds.split(":"))
    sensor = SENSORS[args.sensor]
    if args.oracle == "pyccd" and args.sensor != "landsat-ard":
        ap.error("--oracle pyccd supports landsat-ard only "
                 "(pyccd's detect takes the 7 fixed band keywords)")
    oracle = detect_sensor if args.oracle == "reference" else pyccd_oracle()
    if not args.mode_diff:
        # Pin BOTH sides to the chosen mode explicitly — never rely on
        # the ambient default (the kernel reads FIREBIRD_VARIOGRAM at
        # trace time, the oracle resolves None from the same helper).
        import functools

        os.environ["FIREBIRD_VARIOGRAM"] = args.variogram
        if args.oracle == "reference":
            oracle = functools.partial(
                detect_sensor,
                adjusted_variogram=args.variogram == "adjusted")
    total_bad = swept = 0
    for seed in range(lo, hi):
        bad = run_grid(seed, sensor, args.pixels, args.compare_f32, oracle,
                       mode_diff=args.mode_diff)
        if bad is None:
            continue
        swept += 1
        total_bad += bad
    kind = "mode-diff pixels" if args.mode_diff else "divergences"
    print(f"SWEEP COMPLETE: {total_bad} {kind} over {swept} grids "
          f"x {args.pixels} px ({swept * args.pixels} pixels, "
          f"sensor={sensor.name}, variogram={args.variogram}, "
          f"{hi - lo - swept} grids skipped)")
    return 1 if (total_bad and not args.mode_diff) else 0


if __name__ == "__main__":
    sys.exit(main())
