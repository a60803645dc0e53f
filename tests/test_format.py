"""Format layer tests, mirroring the reference's golden format fixture
(test/test_pyccd.py:37-126)."""

import datetime

import numpy as np

from firebird_tpu.ccd import format as fmt
from firebird_tpu.ccd import params
from firebird_tpu.ccd.sensor import LANDSAT_ARD


def test_format_golden():
    """Hand-built ccdresult -> exact expected row (the reference's golden
    test, adapted: same fields, same date conversion, same flattening)."""
    fval = 0.5
    sday, eday, bday = 1, 3, 2
    band_model = {"magnitude": fval, "rmse": fval,
                  "coefficients": (fval, fval), "intercept": fval}
    cm = {"start_day": sday, "end_day": eday, "break_day": bday,
          "observation_count": 3, "change_probability": fval,
          "curve_qa": fval,
          **{name: band_model for name in params.BAND_NAMES}}
    rows = fmt.format_records(
        cx=100, cy=-100, px=50, py=-50, dates=[sday, bday, eday],
        ccdresult={"processing_mask": [0, 1, 0], "change_models": [cm]})

    iso = lambda o: datetime.date.fromordinal(o).isoformat()
    expected = {"cx": 100, "cy": -100, "px": 50, "py": -50,
                "sday": iso(sday), "eday": iso(eday), "bday": iso(bday),
                "chprob": fval, "curqa": fval,
                "dates": [iso(sday), iso(bday), iso(eday)],
                "mask": [0, 1, 0]}
    for p in LANDSAT_ARD.store_prefixes:
        expected[f"{p}mag"] = fval
        expected[f"{p}rmse"] = fval
        expected[f"{p}coef"] = (fval, fval)
        expected[f"{p}int"] = fval
    assert rows[0] == expected


def test_format_default_sentinel():
    """No change models -> sentinel row sday=eday=bday=day 1
    (ccdc/pyccd.py:99-103)."""
    rows = fmt.format_records(cx=1, cy=2, px=3, py=4, dates=[5, 6],
                              ccdresult={"change_models": [],
                                         "processing_mask": [0, 0]})
    assert len(rows) == 1
    assert rows[0]["sday"] == rows[0]["eday"] == rows[0]["bday"] == "0001-01-01"
    assert rows[0]["chprob"] is None
    assert rows[0]["blcoef"] is None


def test_default_passthrough():
    assert fmt.default([]) == [{"start_day": 1, "end_day": 1, "break_day": 1}]
    assert fmt.default(["x"]) == ["x"]
