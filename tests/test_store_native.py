"""The sqlite store's native bulk insert (native/sqlite.py) against its
Python path: the same frames store the same rows, cell for cell
(``typeof`` and ``quote``), a frame only a Python binding can take
stays on the Python path, and failures, locks and close behave as the
Python path's do."""

import dataclasses
import os
import sqlite3
import threading
import types

import numpy as np
import pytest

from firebird_tpu.ccd import format as ccdformat
from firebird_tpu.ccd.sensor import LANDSAT_ARD, SENTINEL2
from firebird_tpu.ingest.packer import PackedChips
from firebird_tpu.native import sqlite as native_sqlite
from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.retry import RetryPolicy
from firebird_tpu.store import AsyncWriter, SqliteStore, schema


def fake_batch(sensor, side=6, T=30, S=3, seed=0):
    """``format.batch_frames`` of two chips of ``side`` x ``side`` pixels:
    chip 0 with 0 to S segments a pixel (sentinel rows among them), chip 1
    with no observations (empty masks, every pixel a sentinel row)."""
    rng = np.random.default_rng(seed)
    sensor = dataclasses.replace(sensor, chip_side=side)
    B, C, P = len(sensor.store_prefixes), 2, side * side
    d0 = 730000
    dates = np.zeros((C, T), np.int32)
    dates[0] = d0 + 16 * np.arange(T)
    p = PackedChips(cids=np.array([[1515, -3015], [4515, -3015]], np.int64),
                    dates=dates, spectra=None, qas=None,
                    n_obs=np.array([T, 0], np.int32), sensor=sensor)
    nseg = rng.integers(0, S + 1, (C, P)).astype(np.int32)
    nseg[1] = 0
    meta = np.zeros((C, P, S, 6), np.float32)
    meta[..., 0] = d0 + rng.integers(0, 16 * T, (C, P, S))
    meta[..., 1] = meta[..., 0] + 200
    meta[..., 2] = meta[..., 1]
    meta[..., 3] = rng.random((C, P, S))
    meta[..., 4] = rng.integers(0, 30, (C, P, S))
    seg = types.SimpleNamespace(
        n_segments=nseg, seg_meta=meta,
        seg_rmse=rng.random((C, P, S, B), dtype=np.float32),
        seg_mag=rng.standard_normal((C, P, S, B), dtype=np.float32),
        seg_coef=rng.standard_normal((C, P, S, B, 8), dtype=np.float32),
        mask=rng.random((C, P, T)) < 0.7)
    return ccdformat.batch_frames(p, seg)


def product_frame():
    """Two rows as ``products.save_chip_raster`` writes them: object TEXT
    keys and I32S cells given as a list."""
    cells = np.empty(2, object)
    cells[0] = list(range(-5, 95))
    cells[1] = np.arange(100, dtype=np.int32)[::-1].tolist()
    return {"name": np.array(["cover", "change-day"], object),
            "date": np.array(["2001-07-01", "2001-07-01"], object),
            "cx": np.array([1515, 1515], np.int64),
            "cy": np.array([-3015, -3015], np.int64), "cells": cells}


def ragged_pixel_frame():
    masks = np.empty(2, object)
    masks[0] = np.ones(3, np.uint8)
    masks[1] = np.ones(4, np.uint8)
    return {"cx": np.array([1, 1]), "cy": np.array([2, 2]),
            "px": np.array([3, 4]), "py": np.array([5, 5]), "mask": masks}


def frames_of(sensor, table):
    return [(table, f[table]) for _, f in fake_batch(sensor)]


# The frames of each case; NATIVE names the cases the native path takes.
CASES = {
    "landsat-chip": lambda: frames_of(LANDSAT_ARD, "chip"),
    "landsat-pixel": lambda: frames_of(LANDSAT_ARD, "pixel"),
    "landsat-segment": lambda: frames_of(LANDSAT_ARD, "segment"),
    "sentinel2-pixel": lambda: frames_of(SENTINEL2, "pixel"),
    "sentinel2-segment": lambda: frames_of(SENTINEL2, "segment"),
    "product": lambda: [("product", product_frame())],
    "tile-lists": lambda: [("tile", {     # as rf.pipeline.save_model
        "tx": [1], "ty": [2], "name": ["rf"], "model": ['{"trees": 3}'],
        "updated": ["2020-01-01T00:00:00+00:00"]})],
    "text-not-str": lambda: [("tile", {
        "tx": np.array([1]), "ty": np.array([2]),
        "name": np.array([7], object), "model": np.array(["m"], object),
        "updated": np.array(["2020-01-01"], object)})],
    "ragged-masks": lambda: [("pixel", ragged_pixel_frame())],
    "tile-utf8-arrays": lambda: [("tile", {
        "tx": np.array([1, 1, 2]), "ty": np.array([2, 3, 2]),
        "name": np.array(["rf", "Zürich—model", ""], object),
        "model": np.array(["m", None, "ж"], object),
        "updated": np.array(["a", "b", "c"])})],
    "segment-without-rfrawp": lambda: [
        (t, {k: v for k, v in f.items() if k != "rfrawp"})
        for t, f in frames_of(LANDSAT_ARD, "segment")],
}
NATIVE = {"landsat-pixel", "landsat-segment", "sentinel2-pixel",
          "sentinel2-segment", "product", "tile-utf8-arrays",
          "segment-without-rfrawp"}


def dump(path: str) -> dict:
    """Every row of every table, each cell as (typeof, quote)."""
    con = sqlite3.connect(path)
    try:
        out = {}
        for t in schema.TABLES:
            cols = [r[1] for r in con.execute(f'PRAGMA table_info("{t}")')]
            if cols:
                sel = ", ".join(f'typeof("{c}"), quote("{c}")' for c in cols)
                out[t] = sorted(con.execute(f'SELECT {sel} FROM "{t}"'))
        return out
    finally:
        con.close()


def no_native(monkeypatch):
    """FIREBIRD_NO_NATIVE=1, read afresh by the loader."""
    monkeypatch.setenv("FIREBIRD_NO_NATIVE", "1")
    monkeypatch.setattr(native_sqlite, "_lib", None)
    monkeypatch.setattr(native_sqlite, "_tried", False)


def n_rows(frame) -> int:
    return len(next(iter(frame.values())))


def write_all(path, frames):
    """The frames written to a new store: (store_rows_native, its rows)."""
    obs_metrics.reset_registry()
    store = SqliteStore(path, "ks")
    try:
        for table, frame in frames:
            assert store.write(table, frame) == n_rows(frame)
    finally:
        store.close()
    return obs_metrics.get_registry().snapshot()["counters"].get(
        "store_rows_native", 0), dump(store.path)


def test_the_library_builds():
    assert native_sqlite.available()


@pytest.mark.parametrize("case", list(CASES))
def test_native_rows_equal_python_rows(tmp_path, monkeypatch, case):
    frames = CASES[case]()
    rows = sum(n_rows(f) for _, f in frames)
    native_n, native_rows = write_all(str(tmp_path / "native.db"), frames)
    no_native(monkeypatch)
    python_n, python_rows = write_all(str(tmp_path / "python.db"), frames)
    assert native_n == (rows if case in NATIVE else 0)
    assert python_n == 0
    assert native_rows == python_rows
    assert sum(len(v) for v in native_rows.values()) == rows


def test_sentinel_rows_bind_null():
    """The cases above hold what they claim: sentinel rows with NaN REAL
    and None curqa / coefficients / rfrawp, and zero-length masks."""
    (_, f0), (_, f1) = fake_batch(SENTINEL2)
    seg = f0["segment"]
    sentinel = seg["sday"] == "0001-01-01"
    assert sentinel.any() and (~sentinel).any()
    assert np.isnan(seg[f"{SENTINEL2.store_prefixes[0]}mag"][sentinel]).all()
    assert all(v is None for v in seg["curqa"][sentinel])
    assert all(v is None for v in seg["rfrawp"])
    assert all(len(m) == 0 for m in f1["pixel"]["mask"])


def test_native_write_waits_for_the_write_lock(tmp_path):
    """A second connection holding the write lock makes the native write
    wait on the busy timeout, and it lands once the lock is released."""
    (_, frames), _ = fake_batch(LANDSAT_ARD)
    store = SqliteStore(str(tmp_path / "s.db"), "ks")
    holder = sqlite3.connect(store.path, isolation_level=None)
    holder.execute("BEGIN IMMEDIATE")
    done = threading.Event()
    errors = []

    def write():
        try:
            store.write("pixel", frames["pixel"])
        except Exception as e:       # surfaced by the assert below
            errors.append(e)
        done.set()

    t = threading.Thread(target=write)
    t.start()
    try:
        assert not done.wait(0.5)    # waiting on the lock, not failed
    finally:
        holder.execute("COMMIT")
        holder.close()
    t.join(timeout=30)
    assert not t.is_alive() and not errors
    assert store.count("pixel") == len(frames["pixel"]["cx"])
    store.close()


@pytest.mark.parametrize("path", ["native", "python"])
def test_a_failed_frame_rolls_back_and_retries_as_before(
        tmp_path, monkeypatch, path):
    """A frame that fails part-way leaves no row of it, on either path;
    the error is the same sqlite3.Error subclass with sqlite's message,
    and the writer's retry policy spends the same attempts on it."""
    if path == "python":
        no_native(monkeypatch)
    (_, frames), _ = fake_batch(LANDSAT_ARD)
    pixel = frames["pixel"]
    store = SqliteStore(str(tmp_path / "s.db"), "ks")
    con = sqlite3.connect(store.path)
    con.execute("CREATE TRIGGER refuse BEFORE INSERT ON pixel WHEN "
                f"NEW.px = {int(pixel['px'][5])} "
                "BEGIN SELECT RAISE(ABORT, 'refused row'); END")
    con.commit()
    con.close()
    with pytest.raises(sqlite3.IntegrityError, match="refused row"):
        store.write("pixel", pixel)
    assert store.count("pixel") == 0

    obs_metrics.reset_registry()
    w = AsyncWriter(store, retry=RetryPolicy(
        2, sleep=lambda s: None, counter_name="store_write_retries"))
    w.write("pixel", pixel)
    with pytest.raises(sqlite3.IntegrityError, match="refused row"):
        w.flush()
    w.close()
    assert obs_metrics.counter("store_write_retries").value == 2
    assert store.count("pixel") == 0
    # The lock is free: the next frame lands on the same connection.
    store.write("pixel", {k: v[:5] for k, v in pixel.items()})
    assert store.count("pixel") == 5
    store.close()


def test_close_closes_the_native_handles(tmp_path):
    """close() shuts every thread's native connection: the WAL file goes
    with the last connection, and a closed handle refuses a write."""
    (_, frames), _ = fake_batch(LANDSAT_ARD)
    store = SqliteStore(str(tmp_path / "s.db"), "ks")
    t = threading.Thread(target=store.write, args=("pixel", frames["pixel"]))
    t.start()
    t.join(timeout=30)
    store.write("segment", frames["segment"])
    natives = [c for c in store._all_conns
               if isinstance(c, native_sqlite.Connection)]
    assert len(natives) == 2                   # one per writing thread
    assert os.path.exists(store.path + "-wal")
    store.close()
    assert not os.path.exists(store.path + "-wal")
    with pytest.raises(sqlite3.ProgrammingError):
        natives[0].insert("SELECT 1", [], 0)


@pytest.mark.parametrize("native", [True, False])
def test_native_row_counter(tmp_path, monkeypatch, native):
    """Through the writer, store_rows_native is every row but the chip
    frames' (their dates are JSON) with the native path, 0 without."""
    if not native:
        no_native(monkeypatch)
    obs_metrics.reset_registry()
    store = SqliteStore(str(tmp_path / "s.db"), "ks")
    w = AsyncWriter(store)
    batch = fake_batch(LANDSAT_ARD)
    for cid, frames in batch:
        for t in ("chip", "pixel", "segment"):
            w.write(t, frames[t], key=cid)
    w.flush()
    w.close()
    store.close()
    counters = obs_metrics.get_registry().snapshot()["counters"]
    written = counters["store_rows_written"]
    assert written == sum(len(f[t]["cx"]) for _, f in batch
                          for t in ("chip", "pixel", "segment"))
    assert counters.get("store_rows_native", 0) == \
        (written - len(batch) if native else 0)


@pytest.mark.parametrize("code, cls", [
    (5, sqlite3.OperationalError), (6, sqlite3.OperationalError),
    (261, sqlite3.OperationalError), (19, sqlite3.IntegrityError),
    (1811, sqlite3.IntegrityError), (11, sqlite3.DatabaseError),
    (18, sqlite3.DataError), (25, sqlite3.InterfaceError)])
def test_sqlite_codes_raise_pythons_classes(code, cls):
    """sqlite's (extended) result codes raise the class Python's sqlite3
    raises for them: 'database is locked' stays an OperationalError, which
    the setup's lock retry (_retry_locked) recognizes."""
    import ctypes

    e = native_sqlite._error(code, ctypes.create_string_buffer(b"msg"))
    assert type(e) is cls and str(e) == "msg"
    assert e.sqlite_errorcode == code


@pytest.mark.parametrize("column", [
    native_sqlite.Column("int", np.zeros(3, np.int32)),
    native_sqlite.Column("real", np.zeros(2)),
    native_sqlite.Column("text", np.zeros(4, np.uint8),
                         np.array([0, 1, 2, 5], np.int64)),
    native_sqlite.Column("blob", np.zeros(4, np.uint8),
                         np.array([0, 3, 2, 4], np.int64)),
    native_sqlite.Column("int", np.zeros(3, np.int64),
                         nulls=np.zeros(2, bool))])
def test_buffers_are_checked_before_the_call(tmp_path, column):
    """A buffer the library would read past, or of the wrong dtype, is
    refused in Python before any pointer crosses."""
    store = SqliteStore(str(tmp_path / "s.db"), "ks")
    try:
        with pytest.raises(ValueError):
            store._native_conn().insert(
                'INSERT INTO chip (cx) VALUES (?)', [column], 3)
    finally:
        store.close()


def test_concurrent_native_writers_all_land(tmp_path):
    """More writer threads than cores, each on its own native connection,
    contend for sqlite's one write lock: every frame lands once, counted
    once, and close() shuts every connection."""
    import sys

    (_, frames), _ = fake_batch(LANDSAT_ARD)
    pixel = frames["pixel"]
    n_threads, per_thread = 2 * (os.cpu_count() or 4), 5
    obs_metrics.reset_registry()
    store = SqliteStore(str(tmp_path / "s.db"), "ks")
    errors = []

    def work(t):
        try:
            for i in range(per_thread):
                store.write("pixel", dict(pixel, cx=pixel["cx"] + 1000 * t,
                                          cy=pixel["cy"] + i))
        except Exception as e:       # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    rows = n_threads * per_thread * len(pixel["cx"])
    assert store.count("pixel") == rows
    assert obs_metrics.counter("store_rows_native").value == rows
    store.close()
    assert not os.path.exists(store.path + "-wal")
