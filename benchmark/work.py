"""The compulsory work of change detection, from the data's shapes alone.

No route, fusion, padding or round structure of the program enters the
count, so no change to the program can move it:

- bytes: every acquisition of every pixel read once (each band as int16,
  its QA as one byte, each date as an int32 ordinal) and the least result
  written once (the processing mask as one bit per acquisition, and one
  segment per pixel: five decision fields plus, per band, eight
  coefficients, an RMSE and a magnitude, as float32);
- operations: every acquisition scored against a model once in every
  detection band (a K-term harmonic evaluated by K multiply-adds, then a
  residual, a division by the scale and a square-and-add).

The least time is the larger of bytes over the memory bandwidth and
operations over the peak rate; ``peak`` is a row of ``peaks.json``.
"""

from __future__ import annotations

import json
import os

K_COEFS = 8          # harmonic model terms (intercept, slope, 3 harmonics)
DECISION_FIELDS = 5  # start, end, break day, curve QA, change probability


def peaks(device_kind: str) -> dict:
    """The peak row of a device kind; a kind not in the table is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"peaks.json ({sorted(table)})")
    return table[device_kind]


def compulsory(pixels: int, acquisitions: int, bands: int,
               detection_bands: int) -> dict:
    """Bytes and operations of ``pixels`` pixels with ``acquisitions``
    dates each (a chip's share: pass the chip's pixel count and its own
    number of dates)."""
    n, P, B = int(acquisitions), int(pixels), int(bands)
    read = P * n * (2 * B + 1) + 4 * n
    written = P * (n / 8.0) + P * 4 * (DECISION_FIELDS + B * (K_COEFS + 2))
    ops = P * n * int(detection_bands) * (2 * K_COEFS + 3)
    return {"bytes": read + written, "ops": float(ops)}


def least_time(work: dict, peak: dict) -> dict:
    """Least seconds for ``work`` on a device with ``peak``, and which
    floor binds."""
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work["ops"] / peak["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_ops),
            "bound": "memory" if t_mem >= t_ops else "compute",
            "memory_s": t_mem, "compute_s": t_ops}
