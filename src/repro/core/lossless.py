"""Stage-2 lossless coders (host side — the I/O boundary, as in CubismZ).

ZLIB at its default level is the paper's production choice; LZMA trades speed
for ~14% CR; BZ2 stands in for the heavier entropy coders.  ``spdp`` is a
light SPDP-style pipeline (byte shuffle + byte-delta + zlib) used for the
Table 2 comparison of coefficient compressors.
"""
from __future__ import annotations

import bz2
import lzma
import zlib

import numpy as np

__all__ = ["METHODS", "encode", "decode"]


def _spdp_encode(buf: bytes) -> bytes:
    a = np.frombuffer(buf, np.uint8).astype(np.int16)
    d = np.diff(a, prepend=np.int16(0)).astype(np.int8).tobytes()
    return zlib.compress(d, 6)


def _spdp_decode(buf: bytes) -> bytes:
    d = np.frombuffer(zlib.decompress(buf), np.int8).astype(np.int16)
    return (np.cumsum(d, dtype=np.int16) & 0xFF).astype(np.uint8).tobytes()


METHODS = {
    "none": (bytes, lambda b: b),
    "zlib": (lambda b: zlib.compress(b, 6), zlib.decompress),
    "zlib1": (lambda b: zlib.compress(b, 1), zlib.decompress),
    "zlib9": (lambda b: zlib.compress(b, 9), zlib.decompress),
    "lzma": (
        lambda b: lzma.compress(b, preset=6),
        lzma.decompress,
    ),
    "lzma9": (
        lambda b: lzma.compress(b, preset=9),
        lzma.decompress,
    ),
    "bz2": (lambda b: bz2.compress(b, 9), bz2.decompress),
    "spdp": (_spdp_encode, _spdp_decode),
}


def encode(buf, method: str = "zlib") -> bytes:
    """Stage-2 code of a payload (any bytes-like buffer) as ``bytes``."""
    return METHODS[method][0](buf)


def decode(buf: bytes, method: str = "zlib") -> bytes:
    return METHODS[method][1](buf)
