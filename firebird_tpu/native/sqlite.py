"""ctypes bindings for the sqlite store's native bulk insert (sqlitebulk.cpp).

A frame whose every column has a bind buffer (:class:`Column`) is written
by ONE foreign call: BEGIN, the INSERT bound and stepped once per row,
COMMIT — with the interpreter lock released for the whole call.  The
library is built on first use like fastpack's (keyed on its source's
hash), linked against the ``libsqlite3`` that Python's ``_sqlite3``
module loaded, so the native writer and the Python readers run one
sqlite.  ``available()`` is False where it cannot be built or loaded,
or under FIREBIRD_NO_NATIVE=1; the store then keeps its Python path.
"""

from __future__ import annotations

import ctypes
import os
import sqlite3
import threading
from typing import NamedTuple

import numpy as np

from firebird_tpu import native

_SRC = os.path.join(native._HERE, "sqlitebulk.cpp")

# Column kinds, as sqlitebulk.cpp's ``Kind``.
KINDS = {"null": 0, "int": 1, "real": 2, "text": 3, "blob": 4}

_lock = threading.Lock()
_lib = None  # guarded-by: _lock
_tried = False  # guarded-by: _lock


class Column(NamedTuple):
    """One column's bind buffers.  ``data``: int64 (``int``), float64
    (``real``: NaN binds NULL) or uint8 bytes (``text``/``blob``: row r is
    ``data[offsets[r]:offsets[r + 1]]``); ``nulls``: bool, True where the
    row binds NULL, or None.  A ``null`` column binds NULL on every row."""
    kind: str
    data: np.ndarray | None = None
    offsets: np.ndarray | None = None
    nulls: np.ndarray | None = None


class _Col(ctypes.Structure):
    _fields_ = [("kind", ctypes.c_int32), ("data", ctypes.c_void_p),
                ("offsets", ctypes.c_void_p), ("nulls", ctypes.c_void_p)]


def _libsqlite3() -> str | None:
    """Path of the libsqlite3 that Python's ``_sqlite3`` module mapped into
    this process; None where the process map does not say."""
    import _sqlite3  # noqa: F401  (maps the library into the process)

    try:
        with open("/proc/self/maps") as f:
            for line in f:
                fields = line.split(maxsplit=5)
                if len(fields) == 6 and os.path.basename(
                        fields[5].strip()).startswith("libsqlite3.so"):
                    return fields[5].strip()
    except OSError:
        pass
    return None


def _load():
    """The ctypes handle, building the library if needed; None = fallback."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        from firebird_tpu.config import env_knob

        if env_knob("FIREBIRD_NO_NATIVE"):
            return None
        so = _libsqlite3()
        lib = so and native.open_library(_SRC, link=(so,))
        if not lib:
            return None
        vp, cp, i32 = ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int
        lib.fb_sqlite_version.argtypes = []
        lib.fb_sqlite_version.restype = i32
        lib.fb_sqlite_open.argtypes = [cp, i32, ctypes.POINTER(vp), cp, i32]
        lib.fb_sqlite_open.restype = i32
        lib.fb_sqlite_close.argtypes = [vp]
        lib.fb_sqlite_close.restype = i32
        lib.fb_sqlite_insert.argtypes = [vp, cp, ctypes.POINTER(_Col),
                                         ctypes.c_int32, ctypes.c_int64,
                                         cp, i32]
        lib.fb_sqlite_insert.restype = i32
        major, minor, patch = sqlite3.sqlite_version_info
        if lib.fb_sqlite_version() != major * 1_000_000 + minor * 1000 \
                + patch:
            return None
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native bulk insert is built and loaded."""
    return _load() is not None


# sqlite primary result code -> the exception class Python's sqlite3
# module raises for it (CPython Modules/_sqlite/util.c).
_ERRORS = {
    2: sqlite3.InternalError, 12: sqlite3.InternalError,
    11: sqlite3.DatabaseError, 18: sqlite3.DataError,
    19: sqlite3.IntegrityError, 20: sqlite3.IntegrityError,
    21: sqlite3.InterfaceError, 25: sqlite3.InterfaceError,
    **{c: sqlite3.OperationalError
       for c in (1, 3, 4, 5, 6, 8, 9, 10, 13, 14, 15, 16, 17)},
}


def _error(code: int, err: ctypes.Array) -> sqlite3.Error:
    e = _ERRORS.get(code & 0xFF, sqlite3.DatabaseError)(
        err.value.decode("utf-8", "replace"))
    e.sqlite_errorcode = code
    return e


class Connection:
    """A native write connection to an existing sqlite file: WAL,
    synchronous=NORMAL, a busy timeout of ``timeout`` seconds.  One thread
    writes through it at a time; ``close()`` from any thread waits for a
    write in flight."""

    def __init__(self, path: str, timeout: float):
        lib = _load()
        if lib is None:
            raise RuntimeError("the native sqlite library is not available")
        self._lib = lib
        self._lock = threading.Lock()
        db, err = ctypes.c_void_p(), ctypes.create_string_buffer(512)
        rc = lib.fb_sqlite_open(os.fsencode(path), int(timeout * 1000),
                                ctypes.byref(db), err, len(err))
        if rc:
            raise _error(rc, err)
        self._db = db  # guarded-by: _lock

    def insert(self, sql: str, columns: list[Column], n: int) -> None:
        """Run ``sql`` once per row of ``columns`` (``n`` rows), all in one
        transaction; on error it rolls back and raises sqlite's error as
        the ``sqlite3.Error`` subclass Python's module would."""
        cols = (_Col * len(columns))()
        for i, c in enumerate(columns):
            _check(c, n)
            cols[i].kind = KINDS[c.kind]
            for field in ("data", "offsets", "nulls"):
                a = getattr(c, field)
                setattr(cols[i], field, None if a is None else a.ctypes.data)
        err = ctypes.create_string_buffer(512)
        with self._lock:
            if self._db is None:
                raise sqlite3.ProgrammingError(
                    "Cannot operate on a closed database.")
            # ``columns`` holds every buffer alive through the call.
            rc = self._lib.fb_sqlite_insert(
                self._db, sql.encode("utf-8"), cols, len(columns), n, err,
                len(err))
        if rc:
            raise _error(rc, err)

    def close(self) -> None:
        with self._lock:
            db, self._db = self._db, None
        if db is not None:
            self._lib.fb_sqlite_close(db)


def _check(c: Column, n: int) -> None:
    """Refuse a buffer the library would read out of bounds."""
    def need(a, dtype, size):
        if a is None or a.dtype != dtype or a.ndim != 1 \
                or not a.flags.c_contiguous or a.size < size:
            raise ValueError(f"{c.kind} column: bad buffer for {n} rows")

    if c.nulls is not None:
        need(c.nulls, np.bool_, n)
    if c.kind == "null":
        return
    if c.kind in ("int", "real"):
        need(c.data, np.int64 if c.kind == "int" else np.float64, n)
        return
    need(c.offsets, np.int64, n + 1)
    need(c.data, np.uint8, 0)
    if n and (c.offsets[0] < 0 or c.offsets[n] > c.data.size
              or (np.diff(c.offsets[:n + 1]) < 0).any()):
        raise ValueError(f"{c.kind} column: offsets outside its bytes")
