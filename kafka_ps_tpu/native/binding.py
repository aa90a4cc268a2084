"""ctypes binding for the native CSV parser (csvparse.cpp).

Loads `libkpscsv.so` from the package directory.  The library is
git-ignored, so whatever sits on disk may have been built from another
revision of the source (a tool that copies the working tree copies the
binary too): the build stamps the binary with the SHA-256 of
csvparse.cpp, and `_load` rebuilds with make unless the file on disk
carries the stamp of the source beside it.  `is_available()` gates
callers; data/stream.py uses the pure-Python parser when it is False
and says so (`status()`), so the framework has no hard native
dependency.
"""

from __future__ import annotations

import ctypes
import dataclasses
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libkpscsv.so")
_SRC = os.path.join(_DIR, "csvparse.cpp")
_lock = threading.Lock()
_lib = None
_build_failed = False
_why_unavailable = ""


def _source_sha256() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _built_from(sha: str) -> bool:
    """Does the library on disk carry the stamp of this source?  Read
    from the file, not through dlopen — a stale library must be
    replaced BEFORE the one load this process gets."""
    try:
        with open(_SO, "rb") as f:
            return f"KPS_SRC_SHA256={sha}".encode() in f.read()
    except OSError:
        return False


class _ParsedCsv(ctypes.Structure):
    _fields_ = [
        ("num_rows", ctypes.c_long),
        ("nnz", ctypes.c_long),
        ("num_features", ctypes.c_long),
        ("row_offsets", ctypes.POINTER(ctypes.c_long)),
        ("keys", ctypes.POINTER(ctypes.c_int)),
        ("vals", ctypes.POINTER(ctypes.c_float)),
        ("labels", ctypes.POINTER(ctypes.c_int)),
    ]


def _load():
    global _lib, _build_failed, _why_unavailable
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        sha = _source_sha256()
        if not _built_from(sha):
            try:
                subprocess.run(["make", "-B", "-C", _DIR, "libkpscsv.so",
                                f"SRC_SHA256={sha}"],
                               check=True, capture_output=True, timeout=120)
            except (OSError, subprocess.SubprocessError) as e:
                _build_failed = True
                _why_unavailable = f"build failed: {e!r}"
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            _build_failed = True
            _why_unavailable = f"load failed: {e!r}"
            return None
        lib.kps_parse_csv.restype = ctypes.POINTER(_ParsedCsv)
        lib.kps_parse_csv.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.kps_free.restype = None
        lib.kps_free.argtypes = [ctypes.POINTER(_ParsedCsv)]
        _lib = lib
        return _lib


def is_available() -> bool:
    return _load() is not None


def status() -> str:
    """Which parser serves ingestion in this process, and why."""
    if is_available():
        return f"native (csvparse.cpp sha256 {_source_sha256()[:12]})"
    return f"python (native parser unavailable: {_why_unavailable})"


@dataclasses.dataclass(frozen=True)
class NativeCsv:
    """CSR view of a parsed CSV: row i's nonzeros are
    keys[row_offsets[i]:row_offsets[i+1]] (same zero-dropping as
    CsvProducer.java:52-57); labels[i] is the last column."""

    row_offsets: np.ndarray   # [num_rows + 1] int64
    keys: np.ndarray          # [nnz] int32
    vals: np.ndarray          # [nnz] float32
    labels: np.ndarray        # [num_rows] int32
    num_features: int

    @property
    def num_rows(self) -> int:
        return len(self.labels)

    def row(self, i: int) -> tuple[dict[int, float], int]:
        lo, hi = self.row_offsets[i], self.row_offsets[i + 1]
        feats = {int(k): float(v)
                 for k, v in zip(self.keys[lo:hi], self.vals[lo:hi])}
        return feats, int(self.labels[i])

    def to_dense(self) -> tuple[np.ndarray, np.ndarray]:
        x = np.zeros((self.num_rows, self.num_features), np.float32)
        rows = np.repeat(np.arange(self.num_rows),
                         np.diff(self.row_offsets))
        x[rows, self.keys] = self.vals
        return x, self.labels.copy()


def parse_csv(path: str, has_header: bool = True) -> NativeCsv:
    """One-pass native parse; raises RuntimeError if the library is
    unavailable or the file is malformed (callers gate on
    is_available() and fall back to the Python parser)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native CSV parser unavailable (no toolchain?)")
    p = lib.kps_parse_csv(path.encode(), 1 if has_header else 0)
    if not p:
        raise RuntimeError(f"native parse failed for {path}")
    try:
        c = p.contents
        n, nnz = c.num_rows, c.nnz
        out = NativeCsv(
            row_offsets=np.ctypeslib.as_array(c.row_offsets,
                                              (n + 1,)).copy(),
            keys=np.ctypeslib.as_array(c.keys, (max(nnz, 1),))[:nnz].copy(),
            vals=np.ctypeslib.as_array(c.vals, (max(nnz, 1),))[:nnz].copy(),
            labels=np.ctypeslib.as_array(c.labels,
                                         (max(n, 1),))[:n].copy(),
            num_features=int(c.num_features),
        )
    finally:
        lib.kps_free(p)
    return out
