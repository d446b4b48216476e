"""ctypes bindings for the native wire codec, with numpy fallbacks.

``libcodec.so`` is built from ``codec.cpp`` on first use (`make -C native`;
the binary is not in git). Where the build or load fails the numpy
implementations serve — identical semantics (round-to-nearest-even bf16,
CRC-32C) but a per-byte Python CRC loop — at a WARNING, and every serving
role prints ``codec_name()`` at start-up so the fallback cannot hide.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libcodec.so")
_lib: Optional[ctypes.CDLL] = None


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    if not os.path.exists(_LIB_PATH):
        try:
            subprocess.run(
                ["make", "-C", _DIR, "-s"], check=True,
                capture_output=True, timeout=60,
            )
        except Exception as exc:
            logger.warning("native codec build failed (%s); numpy fallback",
                           exc)
            return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        lib.fp32_to_bf16.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        lib.bf16_to_fp32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        lib.crc32c.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.crc32c.restype = ctypes.c_uint32
        _lib = lib
        return lib
    except OSError as exc:
        logger.warning("native codec load failed (%s); numpy fallback", exc)
        return None


def have_native() -> bool:
    return _load() is not None


def codec_name() -> str:
    """"native" (libcodec.so) or "numpy-fallback": what the wire uses."""
    return "native" if have_native() else "numpy-fallback"


def fp32_to_bf16_bytes(arr: np.ndarray) -> bytes:
    """fp32 array -> bf16 wire bytes (round-to-nearest-even)."""
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    lib = _load()
    out = np.empty(arr.size, np.uint16)
    if lib is not None:
        lib.fp32_to_bf16(arr.ctypes.data, out.ctypes.data, arr.size)
        return out.tobytes()
    bits = arr.view(np.uint32).reshape(-1)
    nan = (bits & 0x7FFFFFFF) > 0x7F800000
    bias = 0x7FFF + ((bits >> 16) & 1)
    rounded = ((bits + bias) >> 16).astype(np.uint16)
    qnan = ((bits >> 16) | 0x0040).astype(np.uint16)
    return np.where(nan, qnan, rounded).tobytes()


def bf16_bytes_to_fp32(data: bytes, shape) -> np.ndarray:
    """bf16 wire bytes -> fp32 array of `shape`."""
    raw = np.frombuffer(data, np.uint16)
    lib = _load()
    if lib is not None:
        src = np.ascontiguousarray(raw)
        out = np.empty(raw.size, np.float32)
        lib.bf16_to_fp32(src.ctypes.data, out.ctypes.data, raw.size)
        return out.reshape(shape)
    return (raw.astype(np.uint32) << 16).view(np.float32).reshape(shape)


def crc32c(data: bytes) -> int:
    lib = _load()
    if lib is not None:
        buf = (ctypes.c_char * len(data)).from_buffer_copy(data)
        return int(lib.crc32c(buf, len(data)))
    # numpy fallback: table-driven CRC-32C
    table = _py_table()
    crc = np.uint32(0xFFFFFFFF)
    arr = np.frombuffer(data, np.uint8)
    for b in arr:
        crc = table[(crc ^ b) & 0xFF] ^ (crc >> np.uint32(8))
    return int(crc ^ np.uint32(0xFFFFFFFF))


_TABLE = None


def _py_table():
    global _TABLE
    if _TABLE is None:
        poly = np.uint32(0x82F63B78)
        t = np.zeros(256, np.uint32)
        for i in range(256):
            c = np.uint32(i)
            for _ in range(8):
                c = (poly ^ (c >> np.uint32(1))) if (c & np.uint32(1)) else (c >> np.uint32(1))
            t[i] = c
        _TABLE = t
    return _TABLE
