"""Data shuffling and bit zeroing (paper Exp. 2 / Fig. 5).

Byte shuffling transposes the byte planes of a homogeneous value stream so
that "boring" high bytes group together, which substantially improves the
subsequent lossless stage.  Bit zeroing clears the least significant mantissa
bits of the detail coefficients (Z4/Z8 in the paper) — lossy, but below the
PSNR knee it is free CR.  Host (numpy) variants operate on byte buffers for
the I/O path; device (jnp) variants exist for in-situ use inside jit.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

__all__ = [
    "byte_view",
    "byte_shuffle",
    "byte_unshuffle",
    "bit_shuffle",
    "bit_unshuffle",
    "zero_low_bits_np",
    "zero_low_bits",
]


def byte_view(buf) -> np.ndarray:
    """The bytes of ``buf`` as a flat uint8 array, without a copy where the
    buffer is contiguous (an array of any dtype reads as its bytes)."""
    if isinstance(buf, np.ndarray):
        return np.ascontiguousarray(buf).reshape(-1).view(np.uint8)
    return np.frombuffer(buf, dtype=np.uint8)


def byte_shuffle(buf, itemsize: int, out=None) -> bytearray:
    """Byte planes of ``buf``: byte 0 of every item, then byte 1, and so on.

    Each plane is one strided-read, contiguous-write copy into a single
    output, so the work happens in numpy with the GIL released and scales
    on a thread pool.  The output is ``out`` when given (any writable buffer
    of ``buf``'s size), else a new ``bytearray``; its bytes equal
    ``reshape(-1, itemsize).T.tobytes()``.
    """
    a = byte_view(buf)
    if a.size % itemsize:
        raise ValueError(f"buffer size {a.size} not divisible by itemsize {itemsize}")
    if out is None:
        out = bytearray(a.size)
    planes = np.frombuffer(out, np.uint8).reshape(itemsize, -1)
    items = a.reshape(-1, itemsize)
    for b in range(itemsize):
        planes[b] = items[:, b]
    return out


def byte_unshuffle(buf: bytes | np.ndarray, itemsize: int) -> bytes:
    a = np.frombuffer(buf, dtype=np.uint8) if isinstance(buf, (bytes, bytearray)) else np.asarray(buf, np.uint8)
    if a.size % itemsize:
        raise ValueError(f"buffer size {a.size} not divisible by itemsize {itemsize}")
    return a.reshape(itemsize, -1).T.tobytes()


def bit_shuffle(buf: bytes, itemsize: int) -> bytes:
    a = np.frombuffer(buf, dtype=np.uint8)
    bits = np.unpackbits(a.reshape(-1, itemsize), axis=1, bitorder="little")
    return np.packbits(bits.T, bitorder="little").tobytes()


def bit_unshuffle(buf: bytes, itemsize: int) -> bytes:
    a = np.frombuffer(buf, dtype=np.uint8)
    nbits = itemsize * 8
    bits = np.unpackbits(a, bitorder="little").reshape(nbits, -1)
    return np.packbits(bits.T, axis=1, bitorder="little").tobytes()


def zero_low_bits_np(values: np.ndarray, nbits: int) -> np.ndarray:
    """Clear the ``nbits`` least significant bits of float32 values (host)."""
    if nbits == 0:
        return values
    u = values.astype(np.float32).view(np.uint32)
    u = u & np.uint32(~((1 << nbits) - 1) & 0xFFFFFFFF)
    return u.view(np.float32)


def zero_low_bits(values, nbits: int):
    """Device (jnp) variant of :func:`zero_low_bits_np`."""
    if nbits == 0:
        return values
    u = jnp.asarray(values, jnp.float32).view(jnp.uint32)
    u = u & jnp.uint32(~((1 << nbits) - 1) & 0xFFFFFFFF)
    return u.view(jnp.float32)
