"""Native (C++) host-side kernels, loaded via ctypes.

Builds ``libmtnative.so`` from ``mtnative.cpp`` on first import (g++ is
in the base image; there is no pybind11 — C ABI + ctypes per the
environment brief). Every entry point has a pure-Python/numpy fallback so
the framework degrades gracefully if the toolchain is unavailable; the
``NATIVE`` flag reports which path is live.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "mtnative.cpp")


def _build() -> str | None:
    """Compile (or reuse) the shared library; returns its path or None."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_DIR, f"libmtnative-{digest}.so")
    if os.path.exists(so_path):
        return so_path
    # Per-process tmp name: concurrent first-time builds (pytest workers)
    # must not interleave writes into one tmp file.
    tmp = f"{so_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [
                "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                "-o", tmp, _SRC,
            ],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so_path)
        return so_path
    except (subprocess.SubprocessError, OSError):
        return None


_lib = None
_so = _build()
if _so is not None:
    try:
        _lib = ctypes.CDLL(_so)
        _lib.mtn_crc32c.restype = ctypes.c_uint32
        _lib.mtn_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        _lib.mtn_vbyte_encode_i64.restype = ctypes.c_int64
        _lib.mtn_vbyte_encode_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_size_t,
        ]
        _lib.mtn_vbyte_decode_i64.restype = ctypes.c_int64
        _lib.mtn_vbyte_decode_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_size_t,
        ]
        _lib.mtn_lexsort_i64.restype = None
        _lib.mtn_lexsort_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_size_t, ctypes.c_void_p,
        ]
        _lib.mtn_consolidate_i64.restype = ctypes.c_int64
        _lib.mtn_consolidate_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_void_p, ctypes.c_void_p,
        ]
    except OSError:
        _lib = None

NATIVE = _lib is not None

# Which path is live, where an operator can see it: the gauge ships
# with every process's /metrics families (mz_metrics shows the
# coordinator's and, labeled replica=<name>, each replica's), so a
# deployment running the pure-Python fallbacks is not mistaken for a
# slow one.
from ..utils.metrics import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.get_or_create(
    "gauge", "mz_native_kernels",
    "1 when the C++ host kernels (libmtnative) are loaded, 0 when "
    "this process runs their pure-Python fallbacks",
).set(1.0 if NATIVE else 0.0)


def crc32c(data: bytes) -> int:
    if NATIVE:
        return _lib.mtn_crc32c(data, len(data))
    # Fallback: software CRC32C table, built once.
    global _py_crc_table
    try:
        table = _py_crc_table
    except NameError:
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if c & 1 else (c >> 1)
            table.append(c)
        _py_crc_table = table
    crc = 0xFFFFFFFF
    for b in data:
        crc = table[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def vbyte_encode_i64(a: np.ndarray) -> bytes:
    """Zigzag varint delta encoding of an int64 array."""
    a = np.ascontiguousarray(a, np.int64)
    n = len(a)
    if NATIVE:
        cap = 10 * n + 16
        out = np.empty(cap, np.uint8)
        written = _lib.mtn_vbyte_encode_i64(
            a.ctypes.data, n, out.ctypes.data, cap
        )
        assert written >= 0
        return out[:written].tobytes()
    # Fallback — byte-identical to the native path: deltas wrap mod 2^64
    # before zigzag (a delta of exactly ±2^63 encodes differently if
    # zigzagged exactly).
    mask = (1 << 64) - 1
    out = bytearray()
    prev = 0
    for v in a.tolist():
        d = (v - prev) & mask
        z = ((d << 1) & mask) ^ (mask if d >> 63 else 0)
        prev = v
        while True:
            b = z & 0x7F
            z >>= 7
            out.append(b | (0x80 if z else 0))
            if not z:
                break
    return bytes(out)


def vbyte_decode_i64(data: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.int64)
    if NATIVE:
        buf = np.frombuffer(data, np.uint8)
        consumed = _lib.mtn_vbyte_decode_i64(
            buf.ctypes.data if len(buf) else None, len(buf),
            out.ctypes.data, n,
        )
        if consumed < 0:
            raise ValueError("malformed vbyte stream")
        return out
    pos = 0
    prev = 0
    for i in range(n):
        z = 0
        shift = 0
        while True:
            if pos >= len(data) or shift > 63:
                raise ValueError("malformed vbyte stream")
            byte = data[pos]
            pos += 1
            z |= (byte & 0x7F) << shift
            if not byte & 0x80:
                break
            shift += 7
        delta = (z >> 1) ^ -(z & 1)
        prev += delta
        # Wrap to int64 like the native path.
        prev = (prev + (1 << 63)) % (1 << 64) - (1 << 63)
        out[i] = prev
    return out


def lexsort_i64(cols: list[np.ndarray]) -> np.ndarray:
    """Stable lexicographic sort permutation; cols most-significant
    first (np.lexsort order is the reverse)."""
    n = len(cols[0]) if cols else 0
    if not NATIVE or n == 0:
        return (
            np.lexsort([np.ascontiguousarray(c) for c in cols][::-1])
            if cols
            else np.zeros(0, np.int64)
        )
    arrs = [np.ascontiguousarray(c, np.int64) for c in cols]
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data for a in arrs]
    )
    perm = np.empty(n, np.int64)
    _lib.mtn_lexsort_i64(ptrs, len(arrs), n, perm.ctypes.data)
    return perm


def consolidate_i64(
    key_cols: list[np.ndarray], diffs: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host consolidation: returns (row_indices, summed_diffs) for each
    distinct key with nonzero total diff (differential's
    consolidate_updates)."""
    n = len(diffs)
    if NATIVE and n:
        arrs = [np.ascontiguousarray(c, np.int64) for c in key_cols]
        d = np.ascontiguousarray(diffs, np.int64)
        ptrs = (ctypes.c_void_p * len(arrs))(
            *[a.ctypes.data for a in arrs]
        )
        out_rows = np.empty(n, np.int64)
        out_diffs = np.empty(n, np.int64)
        k = _lib.mtn_consolidate_i64(
            ptrs, len(arrs), d.ctypes.data, n,
            out_rows.ctypes.data, out_diffs.ctypes.data,
        )
        return out_rows[:k].copy(), out_diffs[:k].copy()
    # Fallback: numpy lexsort + run sums.
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    arrs = [np.asarray(c, np.int64) for c in key_cols]
    perm = np.lexsort(arrs[::-1])
    sorted_cols = [c[perm] for c in arrs]
    new_run = np.ones(n, bool)
    new_run[1:] = False
    for c in sorted_cols:
        new_run[1:] |= c[1:] != c[:-1]
    group = np.cumsum(new_run) - 1
    sums = np.zeros(int(group[-1]) + 1, np.int64)
    np.add.at(sums, group, np.asarray(diffs, np.int64)[perm])
    firsts = perm[new_run]
    keep = sums != 0
    return firsts[keep], sums[keep]
