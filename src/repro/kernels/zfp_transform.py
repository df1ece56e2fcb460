"""Pallas TPU kernel: zfpx encode stage (block-float + int lifting + reorder).

Fuses the zfpx substage-1 pipeline for a VMEM-resident tile of blocks:
exponent extraction, fixed-point conversion, the ZFP integer lifting
transform along three axes, total-sequency reorder, and the eps-derived
bit-plane truncation (its ``floor(log2 eps)`` an operand in scalar memory,
so one program serves every ``eps``).  Everything is elementwise int32 work on whole vregs
— pure VPU, no divergent control flow (zero cells are handled by masking).

Layout: the wrapper gathers the 4x4x4 cells (an XLA transpose in HBM) into
``(4, 4, 4, B, nc)`` — the three in-cell axes leading, blocks on sublanes
and the ``nc = (n/4)^3`` cells of a block on lanes.  Lifting along any cell
axis is then a combination of four whole slabs, the per-cell max is an
elementwise max over 64 slabs, and the sequency reorder is a static choice
of slab per output row, so the kernel never reshapes or gathers across
lanes.  The quantized coefficients leave the kernel as ``(64, B, nc)`` and
are transposed to the reference's ``(B, nc, 64)`` outside it.

The decode kernel inverts: inverse reorder, inverse lifting, dequantize.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import zfpx as _z

__all__ = ["zfpx_encode_pallas", "zfpx_decode_pallas"]

#: blocks per grid step; a multiple of 8, since blocks sit on sublanes
DEFAULT_TILE_BLOCKS = 8


def _lift_axis(c, axis: int, fn):
    """Apply a 4-vector lifting ``fn`` along leading cell axis ``axis``."""
    parts = [jax.lax.index_in_dim(c, i, axis, keepdims=False) for i in range(4)]
    return jnp.stack(fn(*parts), axis=axis)


def _encode_kernel(e_ref, x_ref, emax_ref, q_ref):
    cells = x_ref[...]                               # (4,4,4, tb, nc) f32
    emax = _z.cell_emax(jnp.max(jnp.abs(cells), axis=(0, 1, 2)))
    q = jnp.round(cells * _z.pow2(_z.SCALE_BITS - emax)).astype(jnp.int32)
    for axis in (0, 1, 2):
        q = _lift_axis(q, axis, _z._lift4)
    p = _z._drop_bits(emax, e_ref[0])
    zero = emax == _z._ZERO_EMAX
    for r, idx in enumerate(_z.sequency_perm().tolist()):
        c = q[idx // 16, (idx // 4) % 4, idx % 4]
        q_ref[r] = jnp.where(zero, 0, (c >> p) << p)
    emax_ref[...] = emax


def _decode_kernel(emax_ref, q_ref, o_ref):
    emax = emax_ref[...]                             # (tb, nc)
    rows = [None] * 64
    for r, idx in enumerate(_z.sequency_perm().tolist()):
        rows[idx] = q_ref[r]
    cells = jnp.stack(rows).reshape(4, 4, 4, *emax.shape)
    for axis in (2, 1, 0):
        cells = _lift_axis(cells, axis, _z._unlift4)
    out = cells.astype(jnp.float32) * _z.pow2(emax - _z.SCALE_BITS)
    o_ref[...] = jnp.where(emax == _z._ZERO_EMAX, 0.0, out)


def _tile(b: int, tile_blocks: int) -> tuple[int, int]:
    """(tile, padded batch): the whole batch when it fits one tile (a block
    dimension equal to the array's passes the tiling rule), else
    ``tile_blocks`` with the batch padded up to a multiple of it."""
    if b <= tile_blocks:
        return b, b
    return tile_blocks, -(-b // tile_blocks) * tile_blocks


def _pad(x, axis: int, size: int):
    extra = size - x.shape[axis]
    if not extra:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def _to_slabs(blocks):
    """(B, n, n, n) -> (4, 4, 4, B, nc) with nc cells in raster order."""
    b, n = blocks.shape[0], blocks.shape[-1]
    m = n // 4
    c = blocks.reshape(b, m, 4, m, 4, m, 4)
    return jnp.transpose(c, (2, 4, 6, 0, 1, 3, 5)).reshape(4, 4, 4, b, m ** 3)


def _from_slabs(slabs, n: int):
    b, m = slabs.shape[3], n // 4
    c = slabs.reshape(4, 4, 4, b, m, m, m)
    return jnp.transpose(c, (3, 4, 0, 5, 1, 6, 2)).reshape(b, n, n, n)


def zfpx_encode_pallas(blocks, log_eps,
                       tile_blocks: int = DEFAULT_TILE_BLOCKS, interpret: bool = True):
    """``log_eps`` is ``repro.core.zfpx.plane_exponent(eps)``, in scalar
    memory, so one program serves every ``eps``."""
    b, n = blocks.shape[0], blocks.shape[-1]
    nc = (n // 4) ** 3
    tb, bp = _tile(b, tile_blocks)
    x = _pad(_to_slabs(jnp.asarray(blocks, jnp.float32)), 3, bp)
    emax, q = pl.pallas_call(
        _encode_kernel,
        grid=(bp // tb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((4, 4, 4, tb, nc), lambda i: (0, 0, 0, i, 0))],
        out_specs=[
            pl.BlockSpec((tb, nc), lambda i: (i, 0)),
            pl.BlockSpec((64, tb, nc), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, nc), jnp.int32),
            jax.ShapeDtypeStruct((64, bp, nc), jnp.int32),
        ],
        interpret=interpret,
        name="zfpx_encode",
    )(jnp.asarray(log_eps, jnp.int32), x)
    return emax[:b], jnp.transpose(q[:, :b], (1, 2, 0))


def zfpx_decode_pallas(emax, q, n: int = 32,
                       tile_blocks: int = DEFAULT_TILE_BLOCKS, interpret: bool = True):
    """The truncation is already applied to ``q``: decode needs ``emax``
    only."""
    b, nc = emax.shape
    tb, bp = _tile(b, tile_blocks)
    e = _pad(jnp.asarray(emax, jnp.int32), 0, bp)
    qs = _pad(jnp.transpose(jnp.asarray(q, jnp.int32), (2, 0, 1)), 1, bp)
    out = pl.pallas_call(
        _decode_kernel,
        grid=(bp // tb,),
        in_specs=[
            pl.BlockSpec((tb, nc), lambda i: (i, 0)),
            pl.BlockSpec((64, tb, nc), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((4, 4, 4, tb, nc), lambda i: (0, 0, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, 4, 4, bp, nc), jnp.float32),
        interpret=interpret,
        name="zfpx_decode",
    )(e, qs)
    return _from_slabs(out[:, :, :, :b], n)
