"""CCDC algorithm parameters.

One place for every constant of the change-detection spec.  Values are
pinned to the published CCDC algorithm (Zhu & Woodcock 2014, "Continuous
change detection and classification of land cover using all available
Landsat data", RSE 144) with the lcmap-pyccd 2018.03.12 parameterization the
reference pins (setup.py:32) where known.  The reference repo itself never
contains these numbers — they lived inside the external pyccd package — so
this module is the authoritative spec for both the NumPy oracle and the TPU
kernel.

Everything is expressed so both implementations can share it: plain floats /
ints / tuples, no callables.
"""

from __future__ import annotations

import numpy as np
from scipy import stats

# ---------------------------------------------------------------------------
# Bands.  Input order follows the reference timeseries contract
# (ccdc/timeseries.py:33-45): blues, greens, reds, nirs, swir1s, swir2s,
# thermals — indexes 0..6.
# ---------------------------------------------------------------------------
NUM_BANDS = 7
BAND_NAMES = ("blue", "green", "red", "nir", "swir1", "swir2", "thermal")
# Plural forms are the data-plane keyword names (ccdc/timeseries.py:33-45)
# and index the spectra axis everywhere.
BAND_NAMES_PLURAL = ("blues", "greens", "reds", "nirs", "swir1s", "swir2s",
                     "thermals")

# Bands used for change scoring (green, red, nir, swir1, swir2).
DETECTION_BANDS = (1, 2, 3, 4, 5)

# Bands used by the Tmask outlier screen (green, swir1).
TMASK_BANDS = (1, 4)

# Valid data ranges; observations outside are treated as unusable.
# Optical bands are scaled reflectance [0, 10000]; thermal is Kelvin*10.
OPTICAL_MIN, OPTICAL_MAX = 0, 10000
THERMAL_MIN, THERMAL_MAX = -9320, 7070
FILL_VALUE = -9999

# ---------------------------------------------------------------------------
# QA.  ARD pixel_qa is bit-packed (see the reference example series values
# 1 / 66 / 322, ccdc/timeseries.py:104-115: 1 = fill bit, 66/322 contain the
# clear bit).
# ---------------------------------------------------------------------------
QA_FILL_BIT = 0
QA_CLEAR_BIT = 1
QA_WATER_BIT = 2
QA_SHADOW_BIT = 3
QA_SNOW_BIT = 4
QA_CLOUD_BIT = 5

# Procedure selection thresholds (Zhu 2014 §3.2; pyccd procedures).
CLEAR_PCT_THRESHOLD = 0.25   # below: not enough clear obs for standard proc
SNOW_PCT_THRESHOLD = 0.75    # above (of snow/(snow+clear)): permanent snow

# ---------------------------------------------------------------------------
# Model structure.
# ---------------------------------------------------------------------------
# Harmonic design: [1, t, cos wt, sin wt, cos 2wt, sin 2wt, cos 3wt, sin 3wt]
# with w = 2*pi / 365.25 and t in ordinal days.
OMEGA = 2.0 * np.pi / 365.25
MAX_COEFS = 8
MIN_COEFS = 4
MID_COEFS = 6

# Coefficient count by observation density: >= 24 obs -> 8 coefs,
# >= 18 -> 6, else 4 (pyccd num-obs factor 3).
NUM_OBS_FACTOR = 3  # num_coefs*3 observations required per tier

# Minimum observations and time span to initialize a model (Zhu 2014 §3.1).
MEOW_SIZE = 12            # minimum observations in an initialization window
INIT_DAYS = 365.25        # minimum time span of the initialization window

# Stability: initial model is unstable if |slope * span| or the first/last
# absolute residual exceeds STABILITY_FACTOR * adjusted-RMSE (Zhu 2014 §3.1).
STABILITY_FACTOR = 3.0

# Number of consecutive exceeding observations that confirm a change.
PEEK_SIZE = 6

# Change score threshold: chi2 inverse CDF at 0.99 with one degree of
# freedom per detection band.
CHISQUARE_PROB = 0.99
CHANGE_THRESHOLD = float(stats.chi2.ppf(CHISQUARE_PROB, len(DETECTION_BANDS)))

# Single-observation outlier threshold (obs removed, not a change):
# the far chi2 tail, as pyccd's T_MAX_CG.
OUTLIER_PROB = 1 - 1e-6
OUTLIER_THRESHOLD = float(stats.chi2.ppf(OUTLIER_PROB, len(DETECTION_BANDS)))

# Refit schedule: refit the running model when the segment has grown to
# REFIT_FACTOR x the observation count at the previous fit (Zhu 2014 §3.3.1).
REFIT_FACTOR = 1.33

# ---------------------------------------------------------------------------
# Fitting.
# ---------------------------------------------------------------------------
# Lasso regularization (sklearn-style objective 1/(2n)||y-Xb||^2 + alpha|b|_1,
# intercept unpenalized).  Solved by cyclic coordinate descent with a fixed
# iteration count so the TPU kernel jits to a static loop.
LASSO_ALPHA = 1.0
LASSO_ITERS = 50

# Tmask robust screen: IRLS (Huber weights) harmonic fit without trend on
# TMASK_BANDS; an observation is an outlier if |residual| exceeds
# TMASK_CONST * max(variogram, rmse) in any Tmask band.
TMASK_COEFS = 5           # [1, cos wt, sin wt, cos 2wt, sin 2wt]
TMASK_CONST = 4.89

# Minimum date gap for a successive-difference pair to enter the ADJUSTED
# variogram (lcmap-pyccd's adjusted_variogram rule, reconstructed — the
# pinned package at reference setup.py:32 is unreachable offline; see
# docs/DIVERGENCE.md #1).  Near-coincident multi-sensor acquisitions
# (combined L7+L8 archives) otherwise crater the madogram denominator.
VARIOGRAM_GAP_DAYS = 30.0
TMASK_IRLS_ITERS = 5
HUBER_K = 1.345


def variogram_adjusted_default() -> bool:
    """Whether the ADJUSTED variogram rule is active (FIREBIRD_VARIOGRAM;
    default 'adjusted').

    The default follows the reconstruction's own conclusion
    (docs/DIVERGENCE.md #1): the reference pins the *ncompare* release of
    lcmap-pyccd (setup.py:32) — the combined-L7+L8 line whose raison
    d'être is exactly the near-coincident-pair correction the adjusted
    rule implements — so the pinned algorithm is taken to run adjusted.
    ``FIREBIRD_VARIOGRAM=plain`` restores the plain madogram; both modes
    hold the full kernel<->oracle parity envelope.  Read at trace time —
    set before the first detect call (one compiled fn per mode).
    """
    from firebird_tpu.config import env_knob

    return env_knob("FIREBIRD_VARIOGRAM") == "adjusted"

def compact_default() -> bool:
    """Whether active-lane compaction runs in the event loop
    (FIREBIRD_COMPACT; default on).

    Compaction periodically permutes the per-pixel loop state so lanes
    whose pixels are still working (phase != DONE) form a dense prefix —
    trailing all-dead lane blocks then cost a per-block predicate in the
    Pallas kernels instead of a Gram build + CD loop, and the long tail
    re-enters a smaller bucketed loop (kernel._detect_batch_impl).
    Results are row-identical either way (the permutation is inverted at
    loop exit).  Read at trace time like FIREBIRD_PALLAS — set before
    the first detect call; explicit ``compact=`` arguments to
    detect_packed/detect_sharded override per call."""
    from firebird_tpu.config import env_knob

    return env_knob("FIREBIRD_COMPACT") not in ("", "0")


def compact_every() -> int:
    """Rounds between compaction checks (FIREBIRD_COMPACT_EVERY,
    default 4, min 1).  A check only permutes when at least 1/16 of a
    chip's lanes died since the last compaction — the gather sweep over
    the carried residents must buy skipped blocks.  Trace-time read."""
    from firebird_tpu.config import env_knob

    return max(int(env_knob("FIREBIRD_COMPACT_EVERY")), 1)


def compact_min_lanes() -> int:
    """Smallest pixel count that builds the bucketed re-entry loop
    (FIREBIRD_COMPACT_MIN_LANES, default 1024).  The cascade is a second
    traced copy of the round body — real lane savings at chip scale
    (P=10000), pure compile cost for the tiny pixel slices the test
    suite dispatches — so small batches keep the single compacted loop.
    Trace-time read; tests crafting small cascades lower it."""
    from firebird_tpu.config import env_knob

    return max(int(env_knob("FIREBIRD_COMPACT_MIN_LANES")), 1)


def compact_floor() -> float:
    """Alive-fraction floor triggering bucketed re-entry
    (FIREBIRD_COMPACT_FLOOR, default 1/8; 0 disables the cascade).
    When every chip's alive count fits the next power-of-two bucket of
    floor*P lanes, the loop exits, survivors (a dense prefix after the
    forced compaction) are sliced into the bucket, and a smaller-shape
    loop finishes them (kernel._detect_batch_impl stage 2).  Trace-time
    read."""
    from firebird_tpu.config import env_knob

    v = float(env_knob("FIREBIRD_COMPACT_FLOOR"))
    return min(max(v, 0.0), 1.0)


# ---------------------------------------------------------------------------
# Curve QA flags (segment provenance), pyccd-style bit values.
# ---------------------------------------------------------------------------
CURVE_QA_INSUF_CLEAR = 1    # fit by the insufficient-clear procedure
CURVE_QA_PERSIST_SNOW = 2   # fit by the permanent-snow procedure
CURVE_QA_INSIDE = 4         # interior segment (bounded by breaks both sides)
CURVE_QA_START = 8          # first segment of the series
CURVE_QA_END = 16           # segment running to the end of the series

# Insufficient-clear procedure: keep non-fill obs whose blue value is below
# median(blue) + INSUF_CLEAR_BLUE_DELTA.
INSUF_CLEAR_BLUE_DELTA = 400.0
