"""Plain CCDC reference: float64 NumPy, one pixel at a time.

The yardstick the benchmark holds the program's stored rows to.  It is a
copy of the program's own per-pixel oracle (Zhu & Woodcock 2014 CCDC with
the lcmap-pyccd 2018.03.12 parameterisation and pyccd's adjusted
variogram), kept here so that no change to the program can move it, and it
imports nothing from the program.  Bands are fitted together (every band's
coordinate-descent update in one vector step), which is the same
arithmetic band by band.

``precision`` selects the arithmetic: ``"float64"`` (the reference) or
``"bfloat16"`` (the control: every stored intermediate — inputs, design,
Gram, coefficients, residuals, scores, variogram — rounded to bfloat16,
accumulation in float64, as a bfloat16 matrix unit with a wide
accumulator would compute it).  The control exists to show that the
comparison in ``compare.py`` fails a program that computed this way.
"""

from __future__ import annotations

import numpy as np
from scipy import stats

# --- lcmap-pyccd 2018.03.12 parameters (Zhu & Woodcock 2014) -------------
OMEGA = 2.0 * np.pi / 365.25
MAX_COEFS, MID_COEFS, MIN_COEFS = 8, 6, 4
NUM_OBS_FACTOR = 3
MEOW_SIZE = 12
INIT_DAYS = 365.25
STABILITY_FACTOR = 3.0
PEEK_SIZE = 6
REFIT_FACTOR = 1.33
LASSO_ALPHA = 1.0
LASSO_ITERS = 50
TMASK_COEFS = 5
TMASK_CONST = 4.89
TMASK_IRLS_ITERS = 5
HUBER_K = 1.345
VARIOGRAM_GAP_DAYS = 30.0
CLEAR_PCT_THRESHOLD = 0.25
SNOW_PCT_THRESHOLD = 0.75
INSUF_CLEAR_BLUE_DELTA = 400.0
OPTICAL_MIN, OPTICAL_MAX = 0, 10000
THERMAL_MIN, THERMAL_MAX = -9320, 7070
QA_FILL_BIT, QA_CLEAR_BIT, QA_WATER_BIT, QA_SNOW_BIT = 0, 1, 2, 4
CURVE_QA_INSUF_CLEAR = 1
CURVE_QA_PERSIST_SNOW = 2
CURVE_QA_INSIDE = 4
CURVE_QA_START = 8
CURVE_QA_END = 16
CHISQUARE_PROB = 0.99
OUTLIER_PROB = 1 - 1e-6


def _bf16(x):
    """Round to the nearest bfloat16 (ties to even), back in float64."""
    a = np.ascontiguousarray(np.asarray(x, np.float64).astype(np.float32))
    b = a.view(np.uint32)
    b = (b + (((b >> 16) & 1) + np.uint32(0x7FFF))) & np.uint32(0xFFFF0000)
    return b.view(np.float32).astype(np.float64)


class Arith:
    """Where a value is stored, it passes through :meth:`q`."""

    def __init__(self, precision: str = "float64"):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.q = _bf16 if precision == "bfloat16" else \
            (lambda x: np.asarray(x, np.float64))


# ---------------------------------------------------------------------------

def _bit(qa, bit):
    return (np.asarray(qa).astype(np.int64) >> bit) & 1 == 1


def design_matrix(t, anchor, ncoef, ar):
    t = np.asarray(t, np.float64)
    ph = OMEGA * np.mod(t, 365.25)
    yr = (t - anchor) / 365.25
    cols = [np.ones_like(yr), yr, np.cos(ph), np.sin(ph), np.cos(2 * ph),
            np.sin(2 * ph), np.cos(3 * ph), np.sin(3 * ph)]
    return ar.q(np.stack(cols[:ncoef], axis=1))


def num_coefs(n):
    if n >= MAX_COEFS * NUM_OBS_FACTOR:
        return MAX_COEFS
    if n >= MID_COEFS * NUM_OBS_FACTOR:
        return MID_COEFS
    return MIN_COEFS


def lasso_bands(X, Y, ar):
    """[nb, p] Lasso coefficients of every band by cyclic coordinate
    descent on G = X'X/n, c = X'y/n (intercept column unpenalised)."""
    n, p = X.shape
    G = ar.q(X.T @ X / n)
    c = ar.q(Y @ X / n)                                    # [nb, p]
    b = np.zeros((Y.shape[0], p))
    diag = np.maximum(np.diag(G), 1e-12)
    for _ in range(LASSO_ITERS):
        for j in range(p):
            rho = c[:, j] - b @ G[j] + diag[j] * b[:, j]
            if j == 0:
                b[:, j] = ar.q(rho / diag[j])
            else:
                b[:, j] = ar.q(np.sign(rho)
                               * np.maximum(np.abs(rho) - LASSO_ALPHA, 0.0)
                               / diag[j])
    return b


class Model:
    def __init__(self, t, Y, ncoef, anchor, ar):
        self.anchor, self.ar = anchor, ar
        X = design_matrix(t, anchor, ncoef, ar)
        beta = lasso_bands(X, Y, ar)
        self.coefs = np.zeros((Y.shape[0], MAX_COEFS))
        self.coefs[:, :ncoef] = beta
        r = ar.q(Y - beta @ X.T)
        self.rmse = ar.q(np.sqrt(np.mean(r * r, axis=1)))

    def resid(self, t, Y):
        X = design_matrix(t, self.anchor, MAX_COEFS, self.ar)
        return self.ar.q(Y - self.coefs @ X.T)


def variogram(t, Y, ar):
    """Adjusted variogram: median |successive difference| over pairs more
    than VARIOGRAM_GAP_DAYS apart (all pairs when none is)."""
    if t.shape[0] < 2:
        return np.ones(Y.shape[0])
    d = np.abs(np.diff(Y, axis=1))
    sel = np.diff(t.astype(np.float64)) > VARIOGRAM_GAP_DAYS
    if np.any(sel):
        d = d[:, sel]
    return ar.q(np.maximum(np.median(d, axis=1), 1e-6))


def change_score(model, vario, t, Y, det):
    r = model.resid(t, Y)
    denom = np.maximum(model.rmse[det], vario[det])
    return model.ar.q(np.sum((r[det] / denom[:, None]) ** 2, axis=0))


def irls_huber(X, y, ar):
    n, p = X.shape
    beta = ar.q(np.linalg.lstsq(X, y, rcond=None)[0])
    for _ in range(TMASK_IRLS_ITERS):
        r = ar.q(y - X @ beta)
        sigma = max(np.median(np.abs(r - np.median(r))) / 0.6745, 1e-6)
        a = np.abs(r) / (HUBER_K * sigma)
        w = np.where(a <= 1.0, 1.0, 1.0 / np.maximum(a, 1e-12))
        Xw = X * w[:, None]
        beta = ar.q(np.linalg.lstsq(ar.q(Xw.T @ X) + 1e-9 * np.eye(p),
                                    ar.q(Xw.T @ y), rcond=None)[0])
    return beta


def tmask_outliers(t, Y, vario, roles, ar):
    X = design_matrix(t, 0.0, TMASK_COEFS + 1, ar)
    X = np.concatenate([X[:, :1], X[:, 2:]], axis=1)
    bad = np.zeros(t.shape[0], bool)
    for b in roles["tmask_bands"]:
        beta = irls_huber(X, Y[b], ar)
        bad |= ar.q(np.abs(Y[b] - X @ beta)) > TMASK_CONST * vario[b]
    return bad


def dedup_first(t, cand):
    keep = cand.copy()
    seen = set()
    for k in np.flatnonzero(cand):
        if int(t[k]) in seen:
            keep[k] = False
        else:
            seen.add(int(t[k]))
    return keep


def _record(model, start, end, brk, n, prob, cqa, mags):
    return dict(start_day=int(start), end_day=int(end), break_day=int(brk),
                observation_count=int(n), change_probability=float(prob),
                curve_qa=int(cqa), coefs=model.coefs.copy(),
                rmse=model.rmse.copy(), magnitude=np.asarray(mags, float))


def _standard(t, Y, usable, roles, ar):
    det = list(roles["detection_bands"])
    thr, thr_out = (float(stats.chi2.ppf(pr, len(det)))
                    for pr in (CHISQUARE_PROB, OUTLIER_PROB))
    nb = Y.shape[0]
    alive = usable.copy()
    idx = np.flatnonzero(usable)
    vario = variogram(t[idx], Y[:, idx], ar)
    anchor = float(t[0]) if t.shape[0] else 0.0
    segs = []
    alive_from = lambda k0: np.flatnonzero(alive[k0:]) + k0
    i = idx[0] if idx.size else t.shape[0]
    first = True
    while True:
        w = alive_from(i)
        if w.size < MEOW_SIZE:
            break
        jj = MEOW_SIZE - 1
        while jj < w.size and t[w[jj]] - t[w[0]] < INIT_DAYS:
            jj += 1
        if jj >= w.size:
            break
        win = w[:jj + 1]
        bad = tmask_outliers(t[win], Y[:, win], vario, roles, ar)
        if bad.any():
            alive[win[bad]] = False
            continue
        model = Model(t[win], Y[:, win], MIN_COEFS, anchor, ar)
        r = model.resid(t[win], Y[:, win])
        span = float(t[win[-1]] - t[win[0]])
        stable = True
        for b in det:
            denom = STABILITY_FACTOR * max(model.rmse[b], vario[b])
            if (abs(model.coefs[b, 1] / 365.25 * span) > denom
                    or abs(r[b, 0]) > denom or abs(r[b, -1]) > denom):
                stable = False
                break
        if not stable:
            nxt = alive_from(win[0] + 1)
            if nxt.size == 0:
                break
            i = nxt[0]
            continue
        inc = list(win)
        n_last = len(inc)
        model = Model(t[inc], Y[:, inc], num_coefs(len(inc)), anchor, ar)
        cur = win[-1] + 1
        while True:
            peek = alive_from(cur)[:PEEK_SIZE]
            if peek.size < PEEK_SIZE:
                n_exceed = 0
                if peek.size:
                    s = change_score(model, vario, t[peek], Y[:, peek], det)
                    n_exceed = int(np.sum(s > thr))
                    for p, sc in zip(peek, s):
                        if sc <= thr:
                            inc.append(p)
                        else:
                            alive[p] = False
                qa = CURVE_QA_END | (CURVE_QA_START if first else 0)
                segs.append(_record(model, t[inc[0]], t[inc[-1]],
                                    t[inc[-1]], len(inc),
                                    n_exceed / PEEK_SIZE, qa, np.zeros(nb)))
                return segs, alive
            s = change_score(model, vario, t[peek], Y[:, peek], det)
            if np.all(s > thr):
                mags = ar.q(np.median(model.resid(t[peek], Y[:, peek]),
                                      axis=1))
                qa = CURVE_QA_START if first else CURVE_QA_INSIDE
                segs.append(_record(model, t[inc[0]], t[inc[-1]], t[peek[0]],
                                    len(inc), 1.0, qa, mags))
                first = False
                i = peek[0]
                break
            if s[0] > thr_out:
                alive[peek[0]] = False
                cur = peek[0] + 1
            else:
                inc.append(peek[0])
                if len(inc) >= REFIT_FACTOR * n_last:
                    model = Model(t[inc], Y[:, inc], num_coefs(len(inc)),
                                  anchor, ar)
                    n_last = len(inc)
                cur = peek[0] + 1
    return segs, alive


def _single(t, Y, usable, cqa, ar):
    idx = np.flatnonzero(usable)
    if idx.size < MEOW_SIZE:
        return [], np.zeros_like(usable)
    model = Model(t[idx], Y[:, idx], num_coefs(idx.size), float(t[0]), ar)
    return [_record(model, t[idx[0]], t[idx[-1]], t[idx[-1]], idx.size, 0.0,
                    cqa, np.zeros(Y.shape[0]))], usable.copy()


def detect(dates, spectra, qas, roles, precision="float64") -> dict:
    """One pixel: ``dates`` [T] ordinal days, ``spectra`` [B, T], ``qas``
    [T]; ``roles`` holds the configuration's band roles.  Returns
    ``{procedure, segments, mask}`` with segment coefficients in the
    internal parametrisation (intercept at the series' first date, slope
    per year), the processing mask in input order."""
    ar = Arith(precision)
    t_in = np.asarray(dates, np.int64)
    order = np.argsort(t_in, kind="stable")
    t = t_in[order]
    Y = ar.q(np.asarray(spectra, np.float64)[:, order])
    qa = np.asarray(qas)[order]
    fill = _bit(qa, QA_FILL_BIT)
    clear = (_bit(qa, QA_CLEAR_BIT) | _bit(qa, QA_WATER_BIT)) & ~fill
    snow = _bit(qa, QA_SNOW_BIT) & ~fill
    n_nonfill, n_clear, n_snow = int((~fill).sum()), int(clear.sum()), \
        int(snow.sum())
    mask = np.zeros(t.shape[0], bool)
    if n_nonfill == 0:
        return dict(procedure="no-data", segments=[],
                    mask=np.zeros(t_in.shape[0], np.int8))
    opt = Y[list(roles["optical_bands"])]
    ok = np.all((opt > OPTICAL_MIN) & (opt < OPTICAL_MAX), axis=0)
    if roles["thermal_bands"]:
        th = Y[list(roles["thermal_bands"])]
        ok &= np.all((th > THERMAL_MIN) & (th < THERMAL_MAX), axis=0)
    if n_clear / n_nonfill >= CLEAR_PCT_THRESHOLD:
        segs, mask = _standard(t, Y, dedup_first(t, clear & ok), roles, ar)
        proc = "standard"
    elif (n_snow / (n_clear + n_snow) if n_clear + n_snow else 0.0) \
            > SNOW_PCT_THRESHOLD:
        segs, mask = _single(t, Y, dedup_first(t, (clear | snow) & ok),
                             CURVE_QA_PERSIST_SNOW, ar)
        proc = "permanent-snow"
    else:
        cand = ~fill & ok
        blue = Y[roles["blue_band"]]
        if cand.any():
            cand &= blue < float(np.median(blue[cand])) \
                + INSUF_CLEAR_BLUE_DELTA
        segs, mask = _single(t, Y, dedup_first(t, cand),
                             CURVE_QA_INSUF_CLEAR, ar)
        proc = "insufficient-clear"
    out = np.zeros(t_in.shape[0], np.int8)
    out[order] = mask.astype(np.int8)
    return dict(procedure=proc, segments=segs, mask=out)
