"""What ``correct`` compares: stored rows against the plain reference.

Both sides are first brought to one record per pixel — the segments in
start-day order, each with its start, end and break day, curve QA, change
probability in sixths, the eight internal coefficients, RMSE and
magnitude of every band — plus the processing mask.  The store side is
read straight from the sqlite file the window wrote (its own decoding of
the packed columns, not the program's reader).

Two numbers come out:

- ``mismatch_px_pct``: the share of sampled pixels whose decisions differ
  (segment count, any day, curve QA, change probability, processing
  mask), or whose rows are missing.
- ``coef_gap``: over the segments whose decisions agree, the widest gap of
  a band's coefficients, RMSE or magnitude.  Coefficients and magnitude
  are measured against the band's scale, the larger of 1 and the
  reference's largest coefficient of that band (a magnitude is a residual
  of a prediction at that scale, and inherits its rounding); RMSE against
  the larger of 1 and the reference RMSE.  A value that is not finite
  (sqlite hands a stored NaN back as NULL) has no gap that any limit
  passes: it reads ``UNBOUNDED``.
"""

from __future__ import annotations

import datetime
import sqlite3
import sys

import numpy as np

NO_DAY = "0001-01-01"
# The gap of a value that is not finite: above every limit, and still a
# number that a JSON line carries.
UNBOUNDED = sys.float_info.max


def _day(iso):
    return None if iso is None else datetime.date.fromisoformat(iso).toordinal()


def _num(v) -> float:
    """A stored REAL; NULL (how sqlite keeps a NaN) reads as NaN."""
    return np.nan if v is None else float(v)


def reference_record(res: dict) -> dict:
    """A :func:`reference.detect` result as a comparable record."""
    segs = [dict(sday=s["start_day"], eday=s["end_day"], bday=s["break_day"],
                 curqa=s["curve_qa"],
                 chprob=int(round(s["change_probability"] * 6)),
                 coefs=np.asarray(s["coefs"], float),
                 rmse=np.asarray(s["rmse"], float),
                 mag=np.asarray(s["magnitude"], float))
            for s in res["segments"]]
    return dict(segments=segs, mask=np.asarray(res["mask"], np.uint8))


def store_records(db_path: str, pixels: list, prefixes: list,
                  anchors: dict) -> dict:
    """Records of ``pixels`` ((cx, cy, px, py) tuples) read from the store
    file.  ``prefixes`` are the store's band column prefixes in band
    order; ``anchors`` maps (cx, cy) to the chip's first acquisition day,
    which turns the stored pyccd convention (slope per day, intercept at
    day 0) back into the internal one.  A pixel with no pixel row is
    left out of the result."""
    cols = ["sday", "eday", "bday", "chprob", "curqa"]
    for p in prefixes:
        cols += [f"{p}coef", f"{p}int", f"{p}rmse", f"{p}mag"]
    out = {}
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        for key in pixels:
            cx, cy, px, py = (int(v) for v in key)
            row = con.execute(
                'SELECT mask FROM "pixel" WHERE cx=? AND cy=? AND px=? '
                'AND py=?', (cx, cy, px, py)).fetchone()
            if row is None:
                continue
            mask = np.frombuffer(row[0] or b"", np.uint8).copy()
            rows = con.execute(
                f'SELECT {", ".join(cols)} FROM "segment" WHERE cx=? AND '
                f'cy=? AND px=? AND py=? ORDER BY sday',
                (cx, cy, px, py)).fetchall()
            anchor = float(anchors[(cx, cy)])
            segs = []
            for r in rows:
                v = dict(zip(cols, r))
                if v["sday"] == NO_DAY:
                    continue
                coefs, rmse, mag = [], [], []
                for p in prefixes:
                    c7 = np.frombuffer(v[f"{p}coef"] or b"", "<f8")
                    if c7.size != 7:
                        c7 = np.full(7, np.nan)
                    slope = c7[0]
                    coefs.append(np.concatenate(
                        [[_num(v[f"{p}int"]) + slope * anchor,
                          slope * 365.25], c7[1:]]))
                    rmse.append(_num(v[f"{p}rmse"]))
                    mag.append(_num(v[f"{p}mag"]))
                segs.append(dict(
                    sday=_day(v["sday"]), eday=_day(v["eday"]),
                    bday=_day(v["bday"]), curqa=v["curqa"],
                    chprob=None if v["chprob"] is None
                    else int(round(v["chprob"] * 6)),
                    coefs=np.asarray(coefs, float),
                    rmse=np.asarray(rmse, float),
                    mag=np.asarray(mag, float)))
            out[key] = dict(segments=segs, mask=mask)
    finally:
        con.close()
    return out


DECISIONS = ("sday", "eday", "bday", "curqa", "chprob")


def decisions_agree(a: dict, b: dict) -> bool:
    if len(a["segments"]) != len(b["segments"]) \
            or a["mask"].shape != b["mask"].shape \
            or not np.array_equal(a["mask"], b["mask"]):
        return False
    return all(sa[f] == sb[f] for sa, sb in zip(a["segments"],
                                                 b["segments"])
               for f in DECISIONS)


def numeric_gap(got: dict, ref: dict) -> float:
    """Widest relative gap of the continuous payload of two records whose
    decisions agree; ``UNBOUNDED`` where either side holds a value that is
    not finite."""
    gap = 0.0
    for sg, sr in zip(got["segments"], ref["segments"]):
        scale = np.maximum(np.abs(sr["coefs"]).max(axis=1), 1.0)
        gaps = np.concatenate([
            (np.abs(sg["coefs"] - sr["coefs"]) / scale[:, None]).ravel(),
            np.abs(sg["mag"] - sr["mag"])
            / np.maximum(np.abs(sr["mag"]), scale),
            np.abs(sg["rmse"] - sr["rmse"])
            / np.maximum(np.abs(sr["rmse"]), 1.0)])
        if not np.isfinite(gaps).all():
            return UNBOUNDED
        gap = max(gap, float(gaps.max()))
    return gap


def compare(got: dict, ref: dict) -> dict:
    """``got`` and ``ref`` map pixel keys to records; every key of ``ref``
    is judged, and one missing from ``got`` counts as a mismatch."""
    bad, gap = 0, 0.0
    for key, r in ref.items():
        g = got.get(key)
        if g is None or not decisions_agree(g, r):
            bad += 1
            continue
        gap = max(gap, numeric_gap(g, r))
    return {"mismatch_px_pct": 100.0 * bad / max(len(ref), 1),
            "coef_gap": gap}
