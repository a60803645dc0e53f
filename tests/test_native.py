"""Native data plane (firebird_tpu/native): C++ <-> NumPy parity.

The C++ library is an accelerator, not a behavior change: every function
must produce byte-identical results to the NumPy fallback, and the package
must work with FIREBIRD_NO_NATIVE=1.
"""

import base64
import os

import numpy as np
import pytest

from firebird_tpu import native


def _reload_fallback(monkeypatch):
    """A second view of the module forced onto the NumPy path."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)


def test_library_builds():
    # g++ is part of the baked toolchain; the library must compile and load.
    assert native.available()


def test_changed_source_hash_forces_rebuild(tmp_path, monkeypatch):
    """The library is keyed on fastpack.cpp's content: a library built
    from other source (here: the same file, one comment longer) is never
    loaded, whatever its mtime — the edited source builds its own."""
    src = tmp_path / "fastpack.cpp"
    src.write_bytes(open(native._SRC, "rb").read())
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.delenv("FIREBIRD_NO_NATIVE", raising=False)
    first = native._lib_path()
    assert native.available() and os.path.exists(first)

    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    os.utime(first)                      # a newer stale library
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    second = native._lib_path()
    assert second != first and not os.path.exists(second)
    assert native.available() and os.path.exists(second)


@pytest.mark.parametrize("n", [0, 1, 2, 3, 57, 20000])
def test_b64_roundtrip(n):
    rng = np.random.default_rng(n)
    raw = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    enc = base64.b64encode(raw)
    assert native.b64_decode(enc) == raw
    assert native.b64_decode(enc.decode()) == raw


def test_b64_whitespace_and_invalid():
    raw = b"hello world!"
    enc = base64.b64encode(raw).decode()
    wrapped = enc[:4] + "\n" + enc[4:8] + " " + enc[8:]
    assert native.b64_decode(wrapped) == raw
    with pytest.raises(ValueError):
        native.b64_decode("@@@@")


def test_b64_int16_payload():
    # The wire shape: 20,000 bytes of little-endian int16 -> [100,100].
    rng = np.random.default_rng(0)
    a = rng.integers(-30000, 30000, (100, 100), dtype=np.int16)
    enc = base64.b64encode(a.astype("<i2").tobytes())
    out = np.frombuffer(native.b64_decode(enc), dtype="<i2").reshape(100, 100)
    np.testing.assert_array_equal(out, a)


@pytest.mark.parametrize("T,cap", [(0, 8), (1, 8), (37, 64), (64, 64)])
def test_pack_spectra_matches_numpy(T, cap):
    rng = np.random.default_rng(T)
    src = rng.integers(-9999, 30000, (7, T, 251), dtype=np.int16)
    got = native.pack_spectra(src, cap, -9999)
    want = np.full((7, 251, cap), -9999, np.int16)
    want[..., :T] = src.transpose(0, 2, 1)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("T,cap", [(0, 8), (37, 64)])
def test_pack_qa_matches_numpy(T, cap):
    rng = np.random.default_rng(T)
    src = rng.integers(0, 2**16, (T, 333), dtype=np.uint16)
    got = native.pack_qa(src, cap, 1)
    want = np.full((333, cap), 1, np.uint16)
    want[:, :T] = src.T
    np.testing.assert_array_equal(got, want)


def test_fallback_b64_strict(monkeypatch):
    """The stdlib fallback matches the native decoder's error contract:
    whitespace skipped, any other invalid character raises ValueError."""
    raw = bytes(range(256)) * 4
    enc = base64.b64encode(raw).decode()
    wrapped = "\n".join(enc[i: i + 76] for i in range(0, len(enc), 76))
    _reload_fallback(monkeypatch)
    assert native.b64_decode(wrapped) == raw
    with pytest.raises(ValueError):
        native.b64_decode("@@@@")
    with pytest.raises(ValueError):
        native.b64_decode("QUJD@@@@RUZH")


def test_fallback_parity(monkeypatch):
    """The NumPy fallback and C++ agree on a full chip-sized workload."""
    rng = np.random.default_rng(7)
    src = rng.integers(-9999, 30000, (7, 120, 10000), dtype=np.int16)
    qa = rng.integers(0, 2**16, (120, 10000), dtype=np.uint16)
    fast_s = native.pack_spectra(src, 128, -9999)
    fast_q = native.pack_qa(qa, 128, 1)
    _reload_fallback(monkeypatch)
    assert not native.available()
    np.testing.assert_array_equal(native.pack_spectra(src, 128, -9999), fast_s)
    np.testing.assert_array_equal(native.pack_qa(qa, 128, 1), fast_q)


def test_pack_uses_out_buffer():
    src = np.zeros((7, 4, 16), np.int16)
    out = np.empty((7, 16, 8), np.int16)
    got = native.pack_spectra(src, 8, -9999, out=out)
    assert got is out
