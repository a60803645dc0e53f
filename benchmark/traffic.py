"""The one traffic generator: a mix file's parameters -> a seeded pool of
chip archives.

A mix (``benchmark/mixes/<name>.json``) is data only: change, cloud,
seasonal-gap and fill fractions, and ``window_chips``, the fixed number of
chips the window's one chunk holds at the benchmark's ``run_seconds`` (a
shorter ``--seconds`` scales it down, in whole batches).  A configuration
(``benchmark/configs/<name>.json``) fixes the sensor geometry, the acquired
range and the platform schedule.  This module turns the two, plus a seed,
into host arrays shaped like the program's ingest contract (spectra
``[B, T, side, side]`` int16, QA ``[T, side, side]`` uint16, ordinal dates
``[T]``), so the harness can serve them through the program's own
``ChipData``.

It is a copy of the program's synthetic source (harmonic landscape, per
pixel level offsets, step changes in a patch, cloudy dates, winter gaps),
vectorised over pixels and extended with the platform acquisition schedule
and a fill mask.  It imports nothing from the program.

Every seed gets the same dates, the same number of changed, filled and
clear pixels per chip, and the same number of step changes; only where and
when they fall, and the noise, move with the seed.
"""

from __future__ import annotations

import datetime
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FILL_VALUE = -9999
QA_FILL = 1 << 0
QA_CLEAR = 1 << 1
QA_CLOUD = 1 << 5
OMEGA = 2.0 * np.pi / 365.25

# Mean reflectance and seasonal amplitude per band role (int16 scale):
# the program's synthetic palette, blue..swir2 then thermal.
OPTICAL_MEANS = (400.0, 600.0, 500.0, 2500.0, 1500.0, 800.0)
OPTICAL_AMPS = (50.0, 80.0, 80.0, 400.0, 250.0, 120.0)
THERMAL_MEAN, THERMAL_AMP = 2900.0, 500.0

MIX_KEYS = {"change_frac", "n_changes", "cloud_frac", "seasonal_gap_frac",
            "fill_frac", "noise", "window_chips", "why"}


def ordinal(iso: str) -> int:
    return datetime.date.fromisoformat(iso[:10]).toordinal()


def acquired_range(acquired: str) -> tuple[int, int]:
    lo, _, hi = acquired.partition("/")
    return ordinal(lo), ordinal(hi)


def schedule(config: dict) -> np.ndarray:
    """Ordinal acquisition dates of one chip: the union of every platform's
    revisit grid inside the acquired range (half-open ``[start, end)``).
    Each platform's grid is ``origin + phase_days + k * revisit_days``;
    platforms that share a phase never overlap in time in a real record,
    so the union has no duplicate date."""
    lo, hi = acquired_range(config["acquired"])
    origin = ordinal(config["schedule_origin"])
    out = []
    for p in config["platforms"]:
        a, b = max(ordinal(p["start"]), lo), min(ordinal(p["end"]), hi)
        first = origin + p["phase_days"]
        k0 = max(0, -(-(a - first) // p["revisit_days"]))
        d = first + p["revisit_days"] * np.arange(k0, k0 + 10_000)
        out.append(d[(d >= a) & (d < b)])
    t = np.unique(np.concatenate(out)).astype(np.int64)
    return t


def load_mix(mix: dict) -> dict:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"unknown mix keys {sorted(unknown)}")
    m = dict(change_frac=0.0, n_changes=1, cloud_frac=0.0,
             seasonal_gap_frac=0.0, fill_frac=0.0, noise=30.0,
             window_chips=20)
    m.update({k: v for k, v in mix.items() if k != "why"})
    for k in ("change_frac", "cloud_frac", "seasonal_gap_frac", "fill_frac"):
        if not 0.0 <= float(m[k]) <= 1.0:
            raise ValueError(f"mix {k}={m[k]} is not a fraction")
    return m


def band_palette(sensor: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per-band (means, amps): optical bands cycle the optical palette,
    thermal bands take the thermal one."""
    B = len(sensor["band_names"])
    means = np.resize(np.array(OPTICAL_MEANS), B).astype(np.float32)
    amps = np.resize(np.array(OPTICAL_AMPS), B).astype(np.float32)
    for b in sensor["thermal_bands"]:
        means[b], amps[b] = THERMAL_MEAN, THERMAL_AMP
    return means, amps


def chip_arrays(config: dict, mix: dict, seed: int, k: int):
    """Pool chip ``k`` of ``seed``: (dates [T] int64, spectra [B, T, s, s]
    int16, qas [T, s, s] uint16)."""
    m = load_mix(mix)
    sn = config["sensor"]
    side = int(sn["chip_side"])
    P = side * side
    B = len(sn["band_names"])
    rng = np.random.default_rng([int(seed) & (2**64 - 1), int(k)])
    t = schedule(config)
    T = t.shape[0]
    ph = (OMEGA * np.mod(t.astype(np.float64), 365.25)).astype(np.float32)
    means, amps = band_palette(sn)

    level = rng.normal(0.0, 60.0, size=P).astype(np.float32)
    spectra = np.empty((B, T, P), np.int16)
    cos = np.cos(ph)[:, None]
    for b in range(B):
        series = rng.standard_normal((T, P), dtype=np.float32)
        series *= np.float32(m["noise"])
        series += level[None, :]
        series += means[b] + amps[b] * cos
        np.clip(series, -32768, 32767, out=series)
        spectra[b] = series.astype(np.int16)

    # Fill: the sea side of a straight coastline, a whole number of pixel
    # columns (or rows) on a seeded side of the chip — exactly fill_frac of
    # the chip, filled on every date.
    n_fill_cols = int(round(m["fill_frac"] * side))
    land = np.ones((side, side), bool)
    if n_fill_cols:
        edge = int(rng.integers(0, 4))
        sea = np.zeros((side, side), bool)
        if edge == 0:
            sea[:, :n_fill_cols] = True
        elif edge == 1:
            sea[:, side - n_fill_cols:] = True
        elif edge == 2:
            sea[:n_fill_cols, :] = True
        else:
            sea[side - n_fill_cols:, :] = True
        land = ~sea

    # Step changes in a square patch inside the land, at dates spread
    # through the middle of the record (a copy of the program's synthetic
    # rule, which keeps shifted values inside the valid ranges).
    if m["change_frac"] > 0:
        rows = np.flatnonzero(land.any(1))
        cols = np.flatnonzero(land.any(0))
        pside = max(1, int(math.sqrt(m["change_frac"] * land.sum())))
        pside = min(pside, rows.size, cols.size)
        r0 = int(rows[0] + rng.integers(0, rows.size - pside + 1))
        c0 = int(cols[0] + rng.integers(0, cols.size - pside + 1))
        patch = np.zeros((side, side), bool)
        patch[r0:r0 + pside, c0:c0 + pside] = True
        patch = patch.reshape(P)
        nch = max(1, int(m["n_changes"]))
        lo, hi = T // 6, 5 * T // 6
        ks = ((lo + (np.arange(nch) + rng.uniform(0.2, 0.8, nch))
               * (hi - lo) / nch).astype(int) if nch > 1
              else np.array([int(rng.integers(T // 4, 3 * T // 4))]))
        cum = np.zeros(B)
        for kk in ks:
            delta = rng.uniform(500, 1000)
            sign = np.where(rng.random(B) < 0.5, -1.0, 1.0)
            sign = np.where(means - amps + cum < delta + 300, 1.0, sign)
            for b in range(B):
                after = spectra[b, kk:]                    # [T - kk, P] view
                shifted = after[:, patch].astype(np.int32) \
                    + int(np.int16(sign[b] * delta))
                after[:, patch] = np.clip(shifted, -32768, 32767)
            cum += sign * delta

    qas = np.full((T, P), QA_CLEAR, np.uint16)
    cloudy = rng.random(T) < m["cloud_frac"]
    if m["seasonal_gap_frac"] > 0:
        doy = np.mod(t.astype(np.float64), 365.25)
        winter = (doy < 75) | (doy > 320)
        cloudy |= winter & (rng.random(T) < m["seasonal_gap_frac"])
    qas[cloudy] = QA_CLOUD
    sea = ~land.reshape(P)
    if sea.any():
        qas[:, sea] = QA_FILL
        spectra[:, :, sea] = FILL_VALUE
    return (t, spectra.reshape(B, T, side, side),
            qas.reshape(T, side, side))


def pool(config: dict, mix: dict, seed: int, n: int,
         workers: int = 8) -> list[tuple]:
    """``n`` distinct chip archives of ``seed``, generated on a few host
    threads (numpy's generators release the interpreter lock)."""
    with ThreadPoolExecutor(max_workers=max(1, min(workers, n))) as ex:
        return list(ex.map(lambda k: chip_arrays(config, mix, seed, k),
                           range(n)))
