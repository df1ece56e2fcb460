"""Pallas TPU kernel: multi-level 3D wavelet transform over a block batch.

TPU adaptation of the paper's core-layer wavelet kernels.  The CPU code uses
4-tap stencil loops; here each 1D lifting step (split, predict, update) is
one dense ``c x c`` matrix — the prediction matrix encodes the interior
stencil *and* the one-sided boundary stencils, so the transform "on the
interval" needs no gather and no divergent control flow.  Per level, the
step is applied along the three axes of the level's leading ``c^3``
sub-cube:

* k (lanes): one matmul of the sub-cube's ``(tb*c*c, c)`` rows;
* j (sublanes): a batched ``c x c`` matmul per (block, i) slice;
* i (leading): the matrix's few nonzeros per column as slab multiply-adds.

Levels are statically unrolled; each level reads its sub-cube from the
output ref and writes it back in place, so the coarser levels recurse on
the leading corner (the Mallat layout) without any scatter.  The per-level
matrices are kernel operands (Pallas forbids captured constants) with a
constant index map — they stay resident.

VMEM budget: a 32-cubed fp32 block occupies 512 KiB of VMEM once its
32-wide last axis is padded to 128 lanes.  Input and output tiles are
double-buffered, so the default tile of 2 blocks holds 4 MiB of buffers
plus about as much in per-level temporaries, inside v5e's 16 MiB scoped
default.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import wavelets as wv

__all__ = ["wavelet3d_forward", "wavelet3d_inverse", "DEFAULT_TILE_BLOCKS"]

DEFAULT_TILE_BLOCKS = 2

_HI = jax.lax.Precision.HIGHEST


@functools.lru_cache(maxsize=None)
def _step_matrix(kind: str, c: int, inverse: bool) -> np.ndarray:
    """``A`` with ``step(x) = x @ A`` for one 1D lifting step of length ``c``
    along an axis: ``x -> [s | d]`` forward, its inverse otherwise."""
    m = c // 2
    idx, W = wv._predict_table(kind, m)
    P = np.zeros((m, m))
    for i in range(m):
        P[i, idx[i]] += W[i]
    E = np.eye(c)[:, 0::2]                      # x @ E = evens
    O = np.eye(c)[:, 1::2]                      # x @ O = odds
    S = E if kind in ("w4i", "w4l") else (E + O) / 2
    D = O - S @ P.T
    if kind == "w4l":
        U = np.zeros((m, m))
        for i in range(m):
            U[i, i] += 0.25
            U[i, max(i - 1, 0)] += 0.25
        S = S + D @ U.T
    A = np.concatenate([S, D], axis=1)
    return (np.linalg.inv(A) if inverse else A).astype(np.float32)


def _along_i(x, A: np.ndarray):
    """``x @ A`` along axis 1 of (tb, c, c, c): slab multiply-adds over the
    nonzeros of each column of the (static) step matrix."""
    slabs = [x[:, i] for i in range(A.shape[0])]
    out = []
    for col in A.T:
        terms = [float(w) * slabs[i] for i, w in enumerate(col) if w != 0.0]
        out.append(functools.reduce(jnp.add, terms))
    return jnp.stack(out, axis=1)


def _along_j(x, a):
    tb, c = x.shape[0], x.shape[-1]
    x3 = x.reshape(tb * c, c, c)
    at = jnp.broadcast_to(a.T, x3.shape)
    y = jax.lax.dot_general(at, x3, (((2,), (1,)), ((0,), (0,))),
                            precision=_HI, preferred_element_type=jnp.float32)
    return y.reshape(x.shape)


def _along_k(x, a):
    c = x.shape[-1]
    y = jnp.dot(x.reshape(-1, c), a, precision=_HI,
                preferred_element_type=jnp.float32)
    return y.reshape(x.shape)


def _kernel(x_ref, *rest, mats, levels: int, inverse: bool):
    o_ref, mat_refs = rest[-1], rest[:-1]
    n = x_ref.shape[-1]
    o_ref[...] = x_ref[...]
    for lvl in (reversed(range(levels)) if inverse else range(levels)):
        c = n >> lvl
        a = mat_refs[lvl][...]
        sub = o_ref[:, :c, :c, :c]
        if inverse:
            sub = _along_i(_along_j(_along_k(sub, a), a), mats[lvl])
        else:
            sub = _along_k(_along_j(_along_i(sub, mats[lvl]), a), a)
        o_ref[:, :c, :c, :c] = sub


def _call(blocks, kind: str, levels: int | None, inverse: bool,
          tile_blocks: int, interpret: bool):
    b, n = blocks.shape[0], blocks.shape[-1]
    levels = wv.default_levels(n, levels)
    tb = min(tile_blocks, b)
    if b % tb:
        tb = 1
    mats = tuple(_step_matrix(kind, n >> lvl, inverse) for lvl in range(levels))
    in_specs = [pl.BlockSpec((tb, n, n, n), lambda i: (i, 0, 0, 0))]
    in_specs += [pl.BlockSpec(M.shape, lambda i: (0, 0)) for M in mats]
    kern = functools.partial(_kernel, mats=mats, levels=levels, inverse=inverse)
    return pl.pallas_call(
        kern,
        grid=(b // tb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, n, n, n), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, jnp.float32),
        interpret=interpret,
        name="wavelet_inverse" if inverse else "wavelet_forward",
    )(jnp.asarray(blocks, jnp.float32), *[jnp.asarray(M) for M in mats])


def wavelet3d_forward(blocks, kind: str = "w3ai", levels: int | None = None,
                      tile_blocks: int = DEFAULT_TILE_BLOCKS, interpret: bool = True):
    """Forward multi-level 3D DWT of (B, n, n, n) blocks via Pallas."""
    return _call(blocks, kind, levels, False, tile_blocks, interpret)


def wavelet3d_inverse(blocks, kind: str = "w3ai", levels: int | None = None,
                      tile_blocks: int = DEFAULT_TILE_BLOCKS, interpret: bool = True):
    return _call(blocks, kind, levels, True, tile_blocks, interpret)
