"""ctypes bindings for the native ingest data plane (fastpack.cpp).

The shared library is compiled on first use (g++, cached next to this
file under a name keyed on a hash of fastpack.cpp, so a checkout only
ever loads a library built from its own source); every entry point has
a NumPy fallback, so the package works — just slower — where no C++
toolchain exists.  ``available()`` reports which path is active;
FIREBIRD_NO_NATIVE=1 forces the fallback (the test suite uses this to
cover both).  The sqlite store's bulk insert (sqlite.py, sqlitebulk.cpp)
is built the same way, as a library of its own.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fastpack.cpp")

_lock = threading.Lock()
# Mutated only inside _load's `with _lock:`; the double-checked fast
# path reads the references lock-free (reads are not lock-checked).
_lib = None  # guarded-by: _lock
_tried = False  # guarded-by: _lock


def _lib_path(src: str | None = None) -> str:
    """The library built from the current ``src`` (fastpack.cpp by
    default).  Keyed on the source's content, not its mtime: a copied
    checkout carries a stale untracked .so whose mtime says nothing about
    what it was built from."""
    src = src or _SRC
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    stem = os.path.splitext(os.path.basename(src))[0]
    return os.path.join(_HERE, f"lib{stem}-{digest}.so")


def _build(lib_path: str, src: str | None = None, link=()) -> bool:
    # Compile to a process-private temp path and rename into place: the
    # in-process lock doesn't cover concurrent builds from sibling worker
    # processes, and rename() is atomic so nobody ever dlopens a
    # half-written library.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           src or _SRC, *link, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.rename(tmp, lib_path)
        return True
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def open_library(src: str | None = None, link=()):
    """ctypes handle of the library built from ``src`` (fastpack.cpp by
    default), compiling it first where no library of that source exists;
    ``link`` names extra objects to link against.  None where it cannot
    be built or loaded."""
    try:
        lib_path = _lib_path(src)
    except OSError:
        return None
    if not os.path.exists(lib_path) and not _build(lib_path, src, link):
        return None
    try:
        return ctypes.CDLL(lib_path)
    except OSError:
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
        lib = open_library()
        if lib is None:
            return None
        i64, u8p = ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8)
        i16p = ctypes.POINTER(ctypes.c_int16)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.fb_b64_decode.argtypes = [ctypes.c_char_p, i64, u8p]
        lib.fb_b64_decode.restype = i64
        lib.fb_pack_spectra.argtypes = [i16p, i64, i64, i64, i64,
                                        ctypes.c_int16, i16p]
        lib.fb_pack_spectra.restype = None
        lib.fb_pack_qa.argtypes = [u16p, i64, i64, i64, ctypes.c_uint16, u16p]
        lib.fb_pack_qa.restype = None
        _lib = lib
        return _lib


def _b64_fallback(data: bytes) -> bytes:
    """Strict stdlib decode matching the native decoder: whitespace is
    skipped (JSON payloads may wrap), any other invalid char raises."""
    import base64
    import binascii

    try:
        return base64.b64decode(data.translate(None, b" \t\r\n"),
                                validate=True)
    except binascii.Error as e:
        raise ValueError(f"invalid base64 payload: {e}") from None


def available() -> bool:
    """True when the C++ library is loaded (False = NumPy fallback)."""
    return _load() is not None


def b64_decode(data: bytes | str) -> bytes:
    """base64 -> raw bytes (native decoder; falls back to the stdlib)."""
    if isinstance(data, str):
        data = data.encode("ascii")
    lib = _load()
    if lib is None:
        return _b64_fallback(data)
    out = np.empty((len(data) // 4 + 1) * 3, np.uint8)
    n = lib.fb_b64_decode(
        data, len(data), out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if n < 0:
        raise ValueError("invalid base64 payload")
    return out[:n].tobytes()


def b64_decode_into(data: bytes | str, out: np.ndarray) -> int:
    """Decode base64 straight into ``out``'s buffer (no intermediate bytes
    object); returns the decoded byte count.  ``out`` must be C-contiguous
    and at least large enough.  Little-endian hosts only — the wire format
    is little-endian int16 and the reinterpret is a plain memory view."""
    if isinstance(data, str):
        data = data.encode("ascii")
    if not out.flags.c_contiguous:
        raise ValueError("out must be C-contiguous")
    # Worst-case output: 3 bytes per 4 chars, minus what padding removes.
    tail = data.rstrip(b" \t\r\n")
    pad = 2 if tail.endswith(b"==") else (1 if tail.endswith(b"=") else 0)
    if out.nbytes < (3 * len(tail)) // 4 - pad:
        raise ValueError(
            f"out too small: {out.nbytes} bytes for {len(tail)} b64 chars")
    lib = _load()
    if lib is None or sys.byteorder != "little":
        raw = _b64_fallback(data)
        flat = out.view(np.uint8).reshape(-1)
        flat[:len(raw)] = np.frombuffer(raw, np.uint8)
        return len(raw)
    n = lib.fb_b64_decode(
        data, len(data), out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if n < 0:
        raise ValueError("invalid base64 payload")
    return n


def pack_spectra(src: np.ndarray, cap: int, fill: int,
                 out: np.ndarray | None = None) -> np.ndarray:
    """[B, T, HW] int16 -> [B, HW, cap] int16 transpose + fill padding."""
    B, T, HW = src.shape
    if cap < T:
        raise ValueError(f"cap {cap} < T {T}")
    src = np.ascontiguousarray(src, np.int16)
    if out is None:
        out = np.empty((B, HW, cap), np.int16)
    if out.shape != (B, HW, cap) or out.dtype != np.int16 \
            or not out.flags.c_contiguous:
        raise ValueError("out must be C-contiguous int16 [B, HW, cap]")
    lib = _load()
    if lib is None:
        out[..., :T] = src.transpose(0, 2, 1)
        out[..., T:] = fill
        return out
    lib.fb_pack_spectra(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        B, T, HW, cap, fill,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)))
    return out


def pack_qa(src: np.ndarray, cap: int, fill: int,
            out: np.ndarray | None = None) -> np.ndarray:
    """[T, HW] uint16 -> [HW, cap] uint16 transpose + fill padding."""
    T, HW = src.shape
    if cap < T:
        raise ValueError(f"cap {cap} < T {T}")
    src = np.ascontiguousarray(src, np.uint16)
    if out is None:
        out = np.empty((HW, cap), np.uint16)
    if out.shape != (HW, cap) or out.dtype != np.uint16 \
            or not out.flags.c_contiguous:
        raise ValueError("out must be C-contiguous uint16 [HW, cap]")
    lib = _load()
    if lib is None:
        out[:, :T] = src.T
        out[:, T:] = fill
        return out
    lib.fb_pack_qa(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        T, HW, cap, fill,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)))
    return out
