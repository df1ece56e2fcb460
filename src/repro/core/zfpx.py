"""``zfpx`` — TPU-adapted ZFP-style fixed-accuracy transform codec.

Keeps ZFP's actual structure (Lindstrom 2014):

1. partition each block into 4x4x4 cells;
2. block-floating-point: common max exponent ``emax`` per cell, fixed-point
   quantization ``q = round(x * 2^(SCALE_BITS - emax))`` into int32;
3. the (range-contracting, near-lossless) ZFP integer lifting transform along
   each axis;
4. total-sequency coefficient ordering;
5. bit-plane truncation derived from the absolute error tolerance ``eps``.

TPU adaptation (see DESIGN.md §3): ZFP's serial group-testing bit-plane coder
is replaced by vectorized plane truncation — every lane of a cell is processed
with identical control flow, so steps 1-5 run as pure jnp (and as the Pallas
kernel in ``repro.kernels``).  The host finalizes with byte-shuffle + ZLIB
(stage 2), which plays the role of ZFP's entropy back-end.

The truncation shift is a *deterministic function of (emax, eps)*, so the
decoder recovers it without side information; only ``emax`` (int8) and the
truncated coefficients travel.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "SCALE_BITS",
    "sequency_perm",
    "cell_emax",
    "pow2",
    "encode",
    "decode",
    "fwd_lift_cell",
    "inv_lift_cell",
]

SCALE_BITS = 28          # q = round(x * 2^(SCALE_BITS - emax)); |q| <= 2^28
_GUARD_BITS = 2          # transform error guard when converting eps -> planes
_ZERO_EMAX = -127        # emax marker for all-zero cells


@functools.lru_cache(maxsize=None)
def sequency_perm() -> np.ndarray:
    """Permutation ordering 4^3 coefficients by total sequency i+j+k."""
    idx = np.arange(64)
    i, j, k = idx // 16, (idx // 4) % 4, idx % 4
    order = np.lexsort((k, j, i, i + j + k))
    return order.astype(np.int32)


def pow2(k):
    """Exact ``2.0 ** k`` in float32 for an int32 array ``k``.

    Built from exponent bits as the product of two normal powers of two, so
    it is exact wherever the result is representable, on every backend (a
    backend's ``exp2`` need not be) and inside a Pallas kernel alike."""
    k = jnp.asarray(k, jnp.int32)
    h = k >> 1

    def bits(e):
        return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)

    return bits(h) * bits(k - h)


def cell_emax(amax):
    """Per-cell block exponent: ``e`` with ``amax = m * 2^e``, ``m`` in
    [0.5, 1) (``frexp``), or ``_ZERO_EMAX`` for an all-zero cell."""
    _, e = jnp.frexp(amax)
    return jnp.where(amax > 0, e, _ZERO_EMAX).astype(jnp.int32)


def _lift4(x, y, z, w):
    """ZFP forward lifting of a 4-vector (int32, range-contracting)."""
    x = x + w; x = x >> 1; w = w - x
    z = z + y; z = z >> 1; y = y - z
    x = x + z; x = x >> 1; z = z - x
    w = w + y; w = w >> 1; y = y - w
    w = w + (y >> 1); y = y - (w >> 1)
    return x, y, z, w


def _unlift4(x, y, z, w):
    y = y + (w >> 1); w = w - (y >> 1)
    y = y + w; w = w << 1; w = w - y
    z = z + x; x = x << 1; x = x - z
    y = y + z; z = z << 1; z = z - y
    w = w + x; x = x << 1; x = x - w
    return x, y, z, w


def _apply_axis(cells, axis, fn):
    c = jnp.moveaxis(cells, axis, -1)
    x, y, z, w = c[..., 0], c[..., 1], c[..., 2], c[..., 3]
    out = jnp.stack(fn(x, y, z, w), axis=-1)
    return jnp.moveaxis(out, -1, axis)


def fwd_lift_cell(cells):
    """Forward 3D lifting over trailing (4,4,4) axes of an int32 array."""
    for ax in (-3, -2, -1):
        cells = _apply_axis(cells, ax, _lift4)
    return cells


def inv_lift_cell(cells):
    for ax in (-1, -2, -3):
        cells = _apply_axis(cells, ax, _unlift4)
    return cells


def _to_cells(blocks):
    b, n = blocks.shape[0], blocks.shape[-1]
    m = n // 4
    c = blocks.reshape(b, m, 4, m, 4, m, 4)
    c = jnp.transpose(c, (0, 1, 3, 5, 2, 4, 6))
    return c.reshape(b, m * m * m, 4, 4, 4)


def _from_cells(cells, n):
    b = cells.shape[0]
    m = n // 4
    c = cells.reshape(b, m, m, m, 4, 4, 4)
    c = jnp.transpose(c, (0, 1, 4, 2, 5, 3, 6))
    return c.reshape(b, n, n, n)


def _drop_bits(emax, eps: float):
    """Truncation shift per cell: deterministic in (emax, eps)."""
    # grid unit is 2^(emax - SCALE_BITS); dropping p planes errs <= ~2^p units.
    log_eps = int(np.floor(np.log2(eps))) if eps > 0 else -126
    p = log_eps - (emax - SCALE_BITS) - _GUARD_BITS
    return jnp.clip(p, 0, 31)


@functools.partial(jax.jit, static_argnames=("eps",))
def encode(blocks, eps: float = 1e-3):
    """blocks (B, n, n, n) float32 -> (emax (B, nc) int32, q (B, nc, 64) int32)."""
    cells = _to_cells(jnp.asarray(blocks, jnp.float32))     # (B, nc, 4,4,4)
    emax = cell_emax(jnp.max(jnp.abs(cells), axis=(-3, -2, -1)))  # (B, nc)
    scale = pow2(SCALE_BITS - emax)
    q = jnp.round(cells * scale[..., None, None, None]).astype(jnp.int32)
    q = fwd_lift_cell(q)
    q = q.reshape(*q.shape[:-3], 64)[..., jnp.asarray(sequency_perm())]
    p = _drop_bits(emax, eps)[..., None]
    q = jnp.where(emax[..., None] == _ZERO_EMAX, 0, (q >> p) << p)
    return emax, q


@functools.partial(jax.jit, static_argnames=("eps", "n"))
def decode(emax, q, eps: float = 1e-3, n: int = 32):
    """Inverse of :func:`encode` -> (B, n, n, n) float32."""
    inv = jnp.argsort(jnp.asarray(sequency_perm()))
    cells = q[..., inv].reshape(*q.shape[:-1], 4, 4, 4)
    cells = inv_lift_cell(cells)
    scale = pow2(emax - SCALE_BITS)
    out = cells.astype(jnp.float32) * scale[..., None, None, None]
    out = jnp.where((emax == _ZERO_EMAX)[..., None, None, None], 0.0, out)
    return _from_cells(out, n)
