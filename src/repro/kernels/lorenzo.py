"""Pallas TPU kernel: fused dual-quantization + 3D Lorenzo (szx encode/decode).

Encode fuses compensated 2eps-grid quantization with the three axis-wise
finite differences; decode fuses three inclusive prefix sums with
dequantization.  Each grid step owns a tile of whole blocks in VMEM.

Layout: the wrapper views each (n, n, n) block as a lane-dense (n, n*n)
slab — axis i on sublanes, (j, k) flattened onto lanes — a free reshape in
HBM.  A shift by ``s`` along k is then a lane roll by ``s``, along j a lane
roll by ``s*n``, and along i a sublane roll by ``s``, each masked where the
shift crosses the block edge.  The diffs are one masked roll per axis; the
prefix sums are log-step (Hillis-Steele) scans of masked rolls.  Integer
adds wrap like ``jnp.cumsum`` on int32, so both directions stay
integer-exact against the reference in ``repro.core.szx``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lorenzo_encode_pallas", "lorenzo_decode_pallas"]

DEFAULT_TILE_BLOCKS = 4


def _shifted(x, s: int, axis: int, pos, n: int):
    """``x`` moved ``s`` places up block axis ``axis`` (0=i, 1=j, 2=k) of the
    (tb, n, n*n) slab layout, zero where the source falls outside the block.
    ``pos`` holds each element's coordinate along that axis."""
    if axis == 0:
        r = pltpu.roll(x, s, 1)
    else:
        r = pltpu.roll(x, s * (n if axis == 1 else 1), 2)
    return jnp.where(pos >= s, r, 0)


def _coords(shape, n: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    return rows, lanes // n, lanes % n


def _enc_kernel(x_ref, o_ref, *, eps: float, n: int):
    x = x_ref[...]
    inv = 1.0 / (2.0 * eps)
    q = jnp.round(x * inv)
    q = (q + jnp.round((x - q * (2.0 * eps)) * inv)).astype(jnp.int32)
    pos = _coords(q.shape, n)
    for axis in (0, 1, 2):
        q = q - _shifted(q, 1, axis, pos[axis], n)
    o_ref[...] = q


def _dec_kernel(r_ref, o_ref, *, eps: float, n: int):
    r = r_ref[...]
    pos = _coords(r.shape, n)
    for axis in (2, 1, 0):
        s = 1
        while s < n:
            r = r + _shifted(r, s, axis, pos[axis], n)
            s *= 2
    o_ref[...] = r.astype(jnp.float32) * (2.0 * eps)


def _call(x, kern, name, out_dtype, eps, tile_blocks, interpret):
    b, n = x.shape[0], x.shape[-1]
    tb = min(tile_blocks, b)
    if b % tb:
        tb = 1
    spec = pl.BlockSpec((tb, n, n * n), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(kern, eps=eps, n=n),
        grid=(b // tb,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, n, n * n), out_dtype),
        interpret=interpret,
        name=name,
    )(x.reshape(b, n, n * n))
    return out.reshape(x.shape)


def lorenzo_encode_pallas(blocks, eps: float = 1e-3,
                          tile_blocks: int = DEFAULT_TILE_BLOCKS, interpret: bool = True):
    return _call(jnp.asarray(blocks, jnp.float32), _enc_kernel,
                 "lorenzo_encode", jnp.int32, eps, tile_blocks, interpret)


def lorenzo_decode_pallas(residuals, eps: float = 1e-3,
                          tile_blocks: int = DEFAULT_TILE_BLOCKS, interpret: bool = True):
    return _call(jnp.asarray(residuals, jnp.int32), _dec_kernel,
                 "lorenzo_decode", jnp.float32, eps, tile_blocks, interpret)
