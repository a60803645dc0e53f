"""Logical table schemas and key design.

Mirrors the reference's Cassandra schema (resources/schema.cql) and table
modules:

- chip    (cx, cy) -> dates[]                 (schema.cql:30-34, ccdc/chip.py)
- pixel   (cx, cy, px, py) -> mask[]          (schema.cql:48-54, ccdc/pixel.py)
- segment (cx, cy, px, py, sday, eday) -> 5 decision columns, 4 per band,
                                              rfrawp  (schema.cql:103-142,
                                              ccdc/segment.py)
- tile    (tx, ty, name) -> model, updated    (schema.cql:13-19, ccdc/tile.py)

The segment table is keyed by sensor: each band adds ``<p>mag``,
``<p>rmse``, ``<p>coef`` and ``<p>int`` under the sensor's store prefix
``p`` (ccd/sensor.py ``store_prefixes``), in band order.  Landsat ARD's
seven prefixes give 28 band columns, with the decisions the reference's
33 model columns: its contract, and ``TABLES["segment"]`` is that table.
Sentinel-2's twelve give 48 band columns.  A store holds one sensor's
segments: it takes the band columns of the first segment frame it is
given, or of the table it reopens, and refuses a frame with others
(store/backends.py).

Column types: INTEGER/REAL/TEXT scalars; JSON for irregular values (ISO
date lists); and packed-array types for the hot egress columns — BITS
(uint8, the per-pixel processing mask), F64S (float64 vectors: model
coefficients, rfrawp), I32S (int32 rasters: product cells).  Packed
columns are raw little-endian bytes in sqlite/cassandra (the egress path
is host-bound: JSON-encoding a 10k-pixel chip's masks alone costs
seconds per chip) and plain lists in parquet/memory; every backend's
read() returns plain lists either way.
"""

from __future__ import annotations

import numpy as np

from firebird_tpu.ccd.sensor import LANDSAT_ARD

# numpy dtypes of the packed-array column types (little-endian on the wire)
PACKED_DTYPES = {"BITS": np.uint8, "F64S": "<f8", "I32S": "<i4"}

# The default segment table's bands: Landsat ARD, the reference's.
LANDSAT_BANDS = LANDSAT_ARD.store_prefixes
# A band's four segment columns: suffix and type.
BAND_COLUMNS = (("mag", "REAL"), ("rmse", "REAL"), ("coef", "F64S"),
                ("int", "REAL"))


def segment_columns(prefixes) -> list[tuple[str, str]]:
    """The segment table's (column, type) list for band ``prefixes``."""
    return ([("cx", "INTEGER"), ("cy", "INTEGER"), ("px", "INTEGER"),
             ("py", "INTEGER"), ("sday", "TEXT"), ("eday", "TEXT"),
             ("bday", "TEXT"), ("chprob", "REAL"), ("curqa", "INTEGER")]
            + [(f"{p}{suffix}", typ) for p in prefixes
               for suffix, typ in BAND_COLUMNS]
            + [("rfrawp", "F64S")])


def band_prefixes(columns) -> tuple[str, ...]:
    """The band prefixes of segment ``columns`` (names, or a frame's
    keys), in their order: one per ``<p>coef`` column."""
    return tuple(c[:-4] for c in columns if c.endswith("coef"))


def require_landsat(seg: dict, reader: str) -> None:
    """Refuse, in a reader of Landsat's segment columns, a segment frame
    whose band columns are another sensor's (instead of reading NULLs)."""
    got = band_prefixes(seg)
    if got and set(got) != set(LANDSAT_BANDS):
        raise ValueError(
            f"{reader} reads Landsat ARD's segment columns {LANDSAT_BANDS}; "
            f"these segments have {got}")


TABLES: dict[str, dict] = {
    "chip": {
        "columns": [("cx", "INTEGER"), ("cy", "INTEGER"), ("dates", "JSON")],
        "key": ("cx", "cy"),
    },
    "pixel": {
        "columns": [("cx", "INTEGER"), ("cy", "INTEGER"), ("px", "INTEGER"),
                    ("py", "INTEGER"), ("mask", "BITS")],
        "key": ("cx", "cy", "px", "py"),
    },
    "segment": {
        "columns": segment_columns(LANDSAT_BANDS),
        "key": ("cx", "cy", "px", "py", "sday", "eday"),
    },
    "tile": {
        "columns": [("tx", "INTEGER"), ("ty", "INTEGER"), ("name", "TEXT"),
                    ("model", "TEXT"), ("updated", "TEXT")],
        "key": ("tx", "ty", "name"),
    },
    # Derived product rasters (the reference 0.5 `ccdc-save` capability,
    # docs/faq.rst:38-109; dropped by 1.0 — completed here, SURVEY.md §2.5).
    # One row per (product, date, chip): row-major [100x100] cell values.
    "product": {
        "columns": [("name", "TEXT"), ("date", "TEXT"), ("cx", "INTEGER"),
                    ("cy", "INTEGER"), ("cells", "I32S")],
        "key": ("name", "date", "cx", "cy"),
    },
}


def primary_key(table: str) -> tuple[str, ...]:
    return TABLES[table]["key"]


def column_types(table: str, prefixes=None) -> dict[str, str]:
    """Column -> type of ``table``; a segment table of band ``prefixes``
    where given, else the default (Landsat) table."""
    if table == "segment" and prefixes is not None:
        return dict(segment_columns(prefixes))
    return dict(TABLES[table]["columns"])


def columns(table: str, prefixes=None) -> list[str]:
    return list(column_types(table, prefixes))
