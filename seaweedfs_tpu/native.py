"""ctypes bindings for the native C++ runtime library (native/weedtpu_native.cc).

The reference gets its CPU performance from native code in dependencies —
klauspost/reedsolomon's AVX2 GF(2^8) assembly (go.mod:61) for erasure coding,
Go's AES-NI stdlib for chunk encryption (weed/util/cipher.go), and hardware
CRC for checksums.  This module is the equivalent seam in this framework: a
small C++ library exposing a C ABI, compiled on first use with the in-repo
Makefile and loaded via ctypes (pybind11 is not in the image).

The Makefile compiles with -march=native and the source picks its AVX2 /
SSE4.2 / AES paths at compile time, so a build is only valid on a CPU with
the features of the one that built it.  The artefact's file name therefore
carries a key over source + Makefile + compiler flags + this host's CPU
features: a library built elsewhere (the chip tool copies the tree as it
stands on disk) has another name and is rebuilt, never dlopened.

Falls back gracefully: `available()` is False when no compiler is present,
and callers (ops.codec registry, utils.cipher) keep a pure-Python/numpy path.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_HERE, "native")


def _cpu_features() -> str:
    """This host's CPU feature list (what -march=native compiles for)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return platform.processor()


def _so_name() -> str:
    """File name of the build that is valid here: keyed on source,
    Makefile, compiler overrides and this host's CPU features."""
    h = hashlib.sha256()
    for name in ("weedtpu_native.cc", "Makefile"):
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    for part in (os.environ.get("CXX", ""), os.environ.get("CXXFLAGS", ""),
                 platform.machine(), _cpu_features()):
        h.update(b"\0" + part.encode())
    return f"libweedtpu_native-{h.hexdigest()[:16]}.so"


_lib = None
_lib_err: str | None = None
_lock = threading.Lock()

_u8p = ctypes.POINTER(ctypes.c_uint8)


class NativeUnavailable(RuntimeError):
    pass


def _build() -> str:
    """Path of the library for this source on this CPU, built if absent."""
    so_name = _so_name()
    so_path = os.path.join(_NATIVE_DIR, so_name)
    if os.path.exists(so_path):
        return so_path
    # serialize concurrent first-use builds across processes (the
    # Makefile renames into place, so nobody dlopens a half-written .so)
    import fcntl
    lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if not os.path.exists(so_path):  # else: built while we waited
                subprocess.run(
                    ["make", "-C", _NATIVE_DIR, f"OUT={so_name}"],
                    check=True, capture_output=True)
                # builds for other sources/flags/CPUs are dead weight now
                for old in glob.glob(os.path.join(
                        _NATIVE_DIR, "libweedtpu_native*.so")):
                    if old != so_path:
                        os.remove(old)
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return so_path


def _load():
    global _lib, _lib_err
    with _lock:
        if _lib is not None or _lib_err is not None:
            return _lib
        try:
            lib = ctypes.CDLL(_build())
        except (OSError, subprocess.CalledProcessError) as e:
            _lib_err = str(e)
            return None
        lib.wn_gf_init()
        lib.wn_gf_mul.restype = ctypes.c_uint8
        lib.wn_gf_mul.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
        lib.wn_gf_mul_slice.argtypes = [
            ctypes.c_uint8, _u8p, _u8p, ctypes.c_size_t, ctypes.c_int]
        lib.wn_gf_matmul.argtypes = [
            _u8p, ctypes.c_int, ctypes.c_int, _u8p, _u8p, ctypes.c_size_t]
        lib.wn_gf_matmul_ptrs.argtypes = [
            _u8p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(_u8p), ctypes.POINTER(_u8p), ctypes.c_size_t]
        lib.wn_gf_set_impl.argtypes = [ctypes.c_int]
        lib.wn_gf_impl.restype = ctypes.c_int
        lib.wn_crc32c.restype = ctypes.c_uint32
        lib.wn_crc32c.argtypes = [_u8p, ctypes.c_size_t, ctypes.c_uint32]
        lib.wn_aes256_ctr.argtypes = [_u8p, _u8p, _u8p, _u8p, ctypes.c_size_t]
        lib.wn_aes256_gcm_seal.argtypes = [
            _u8p, _u8p, _u8p, ctypes.c_size_t, _u8p, _u8p, ctypes.c_size_t, _u8p]
        lib.wn_aes256_gcm_open.restype = ctypes.c_int
        lib.wn_aes256_gcm_open.argtypes = [
            _u8p, _u8p, _u8p, ctypes.c_size_t, _u8p, _u8p, ctypes.c_size_t, _u8p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def load_error() -> str | None:
    _load()
    return _lib_err


def _require():
    lib = _load()
    if lib is None:
        raise NativeUnavailable(
            f"native library unavailable (need g++/make to build "
            f"{_NATIVE_DIR}): {_lib_err}")
    return lib


def _as_u8p(a) -> _u8p:
    return a.ctypes.data_as(_u8p)


GF_IMPL_AUTO, GF_IMPL_AVX2, GF_IMPL_SCALAR, GF_IMPL_GFNI = 0, 1, 2, 3


def gf_impl() -> int:
    """Active GF matmul kernel: 1=AVX2 split-table, 2=scalar, 3=GFNI+AVX512."""
    return int(_require().wn_gf_impl())


def set_gf_impl(impl: int) -> None:
    """Force a kernel (GF_IMPL_*): lets tests run the AVX2 path (the
    klauspost-equivalent baseline) on GFNI hosts. GF_IMPL_AUTO restores
    best-available dispatch."""
    _require().wn_gf_set_impl(int(impl))


def gf_matmul(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[rows, n] = mat[rows, k] @ data[k, n] over GF(2^8) (native AVX2)."""
    lib = _require()
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    rows, k = mat.shape
    k2, n = data.shape
    assert k == k2, (mat.shape, data.shape)
    out = np.empty((rows, n), dtype=np.uint8)
    lib.wn_gf_matmul(_as_u8p(mat), rows, k, _as_u8p(data), _as_u8p(out),
                     ctypes.c_size_t(n))
    return out


def gf_matmul_ptrs(mat: np.ndarray, in_rows: list[np.ndarray],
                   out_rows: list[np.ndarray], n: int) -> None:
    """out_rows[r][:n] = sum_j mat[r, j] * in_rows[j][:n] over GF(2^8).

    Row buffers may be scattered (e.g. views straight into an mmap'd .dat),
    so the encode path runs with zero staging copies.  Each in_rows[j] /
    out_rows[r] must be C-contiguous uint8 with >= n bytes."""
    lib = _require()
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    rows, k = mat.shape
    assert len(in_rows) == k and len(out_rows) == rows, (mat.shape,)
    ins = (_u8p * k)(*[r.ctypes.data_as(_u8p) for r in in_rows])
    outs = (_u8p * rows)(*[r.ctypes.data_as(_u8p) for r in out_rows])
    lib.wn_gf_matmul_ptrs(_as_u8p(mat), rows, k, ins, outs,
                          ctypes.c_size_t(n))


def gf_mul_slice(c: int, src: np.ndarray, dst: np.ndarray,
                 accumulate: bool = False) -> None:
    lib = _require()
    assert src.dtype == np.uint8 and dst.dtype == np.uint8
    assert src.size == dst.size
    lib.wn_gf_mul_slice(c, _as_u8p(src), _as_u8p(dst),
                        ctypes.c_size_t(src.size), 1 if accumulate else 0)


def crc32c(data: bytes | np.ndarray, crc: int = 0) -> int:
    lib = _require()
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else data
    return int(lib.wn_crc32c(_as_u8p(np.ascontiguousarray(arr)),
                             ctypes.c_size_t(arr.size), crc))


def aes256_gcm_seal(key: bytes, nonce: bytes, plaintext: bytes,
                    aad: bytes = b"") -> bytes:
    """Returns ciphertext||tag, mirroring Go's gcm.Seal output layout that
    the reference stores for encrypted chunks (weed/util/cipher.go)."""
    lib = _require()
    assert len(key) == 32 and len(nonce) == 12
    pt = np.frombuffer(plaintext, dtype=np.uint8)
    ct = np.empty(len(plaintext), dtype=np.uint8)
    tag = np.empty(16, dtype=np.uint8)
    k = np.frombuffer(key, dtype=np.uint8)
    nc = np.frombuffer(nonce, dtype=np.uint8)
    ad = np.frombuffer(aad, dtype=np.uint8) if aad else np.empty(0, np.uint8)
    lib.wn_aes256_gcm_seal(_as_u8p(k), _as_u8p(nc), _as_u8p(ad),
                           ctypes.c_size_t(len(aad)), _as_u8p(pt), _as_u8p(ct),
                           ctypes.c_size_t(len(plaintext)), _as_u8p(tag))
    return ct.tobytes() + tag.tobytes()


def aes256_gcm_open(key: bytes, nonce: bytes, sealed: bytes,
                    aad: bytes = b"") -> bytes:
    lib = _require()
    assert len(key) == 32 and len(nonce) == 12 and len(sealed) >= 16
    ct = np.frombuffer(sealed[:-16], dtype=np.uint8)
    tag = np.frombuffer(sealed[-16:], dtype=np.uint8)
    pt = np.empty(len(ct), dtype=np.uint8)
    k = np.frombuffer(key, dtype=np.uint8)
    nc = np.frombuffer(nonce, dtype=np.uint8)
    ad = np.frombuffer(aad, dtype=np.uint8) if aad else np.empty(0, np.uint8)
    rc = lib.wn_aes256_gcm_open(_as_u8p(k), _as_u8p(nc), _as_u8p(ad),
                                ctypes.c_size_t(len(aad)), _as_u8p(ct),
                                _as_u8p(pt), ctypes.c_size_t(ct.size),
                                _as_u8p(tag))
    if rc != 0:
        raise ValueError("cipher: message authentication failed")
    return pt.tobytes()
