"""Block-structured layout for 3D fields (CubismZ cluster/node layer analogue).

A 3D field of shape (nx, ny, nz) is decomposed into cubic blocks of side
``bs`` (power of two).  Blocks are fully independent compression units — the
"on the interval" wavelet property means no halo exchange is required, which
is what makes the scheme embarrassingly parallel in the paper and lets us
``vmap``/Pallas-grid over blocks here.

A field of any shape is split into ``ceil(n / bs)`` blocks per axis.  The
blocks at the far edge of an axis that is not a multiple of ``bs`` are
padded by replicating the field's last plane (numpy's ``mode="edge"``),
which keeps every scheme's error bound on the padded values and costs the
predictors and transforms almost nothing; :func:`unblockify` crops them
off again.  A field whose shape is a multiple of ``bs`` is not padded.
"""
from __future__ import annotations

import itertools

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.obs import trace

__all__ = ["blockify", "unblockify", "num_blocks", "pad_elements",
           "field_header", "check_block_size"]

_PAD_ELEMENTS = obs.counter(
    "cz_blockify_pad_elements_total",
    "Elements blockify added to edge blocks by replicating a field's last "
    "plane (compare cz_stage1_elements_total).")


def check_block_size(bs: int) -> None:
    if bs < 4 or (bs & (bs - 1)) != 0:
        raise ValueError(f"block size must be a power of 2 and >= 4, got {bs}")


def num_blocks(shape: tuple[int, int, int], bs: int) -> tuple[int, int, int]:
    """Blocks per axis, ``ceil(n / bs)``: the last one may be an edge block."""
    check_block_size(bs)
    if any(s < 1 for s in shape):
        raise ValueError(f"field shape {tuple(shape)} has an empty axis")
    return tuple(-(-s // bs) for s in shape)


def pad_elements(shape: tuple[int, int, int], bs: int) -> int:
    """Elements the edge blocks of a field of ``shape`` add to it."""
    return (int(np.prod(num_blocks(shape, bs))) * bs ** 3
            - int(np.prod(shape)))


def field_header(shape, bs: int, itemsize: int) -> dict:
    """The header keys that describe a field of ``shape`` in blocks of
    ``bs``: ``field_shape``; ``"pad": "edge"`` only where edge blocks are
    padded, so a multiple shape's header is as before padding existed; and
    ``raw_bytes``, the field's own points, never the padding."""
    keys = {"field_shape": [int(n) for n in shape]}
    if pad_elements(shape, bs):
        keys["pad"] = "edge"
    keys["raw_bytes"] = int(np.prod(shape)) * int(itemsize)
    return keys


def _block_view(blocks, nb):
    """(n_blocks, bs, bs, bs) viewed as (bx, bs, by, bs, bz, bs): the axes of
    the padded field, split into block index and in-block offset."""
    bs = blocks.shape[-1]
    return blocks.reshape(*nb, bs, bs, bs).transpose(0, 3, 1, 4, 2, 5)


def _pieces(n: int, bs: int):
    """Per axis, ``(block slice, in-block slice, field slice)`` of the whole
    blocks and of the partial last block, where there is one."""
    full, rest = divmod(n, bs)
    out = []
    if full:
        out.append((slice(0, full), slice(0, bs), slice(0, full * bs)))
    if rest:
        out.append((slice(full, full + 1), slice(0, rest),
                    slice(full * bs, n)))
    return out


def _copies(shape, bs):
    """The (block-view index, field index) pairs whose copies move every
    real point of a field between it and its padded blocks, one per
    combination of whole and partial blocks along the three axes."""
    for px, py, pz in itertools.product(*(_pieces(n, bs) for n in shape)):
        view = (px[0], px[1], py[0], py[1], pz[0], pz[1])
        split = tuple(d for p in (px, py, pz)
                      for d in (p[0].stop - p[0].start, p[1].stop))
        yield view, (px[2], py[2], pz[2]), split


def _split(a, shape):
    """``a`` viewed with ``shape``, which only splits its axes: never a
    copy (assigning ``.shape`` raises where a copy would be needed)."""
    v = a.view()
    v.shape = shape
    return v


def blockify(field, bs: int):
    """(nx, ny, nz) -> (n_blocks, bs, bs, bs), C-order block raster; edge
    blocks are padded with the field's last plane along each axis."""
    nx, ny, nz = field.shape
    bx, by, bz = num_blocks((nx, ny, nz), bs)
    xp = jnp if isinstance(field, jnp.ndarray) else np
    pad = [b * bs - n for b, n in zip((bx, by, bz), (nx, ny, nz))]
    if any(pad):
        npad = bx * by * bz * bs ** 3 - nx * ny * nz
        _PAD_ELEMENTS.inc(npad)
        if xp is np:
            return _blockify_padded(np.asarray(field), bs, (bx, by, bz), npad)
        # on the device the pad is dispatched, not run, here: no ``pad``
        # span, which would time the dispatch; the device trace has the op
        field = jnp.pad(field, [(0, p) for p in pad], mode="edge")
    f = field.reshape(bx, bs, by, bs, bz, bs)
    f = xp.transpose(f, (0, 2, 4, 1, 3, 5))
    return f.reshape(bx * by * bz, bs, bs, bs)


def _blockify_padded(field: np.ndarray, bs: int, nb, npad: int) -> np.ndarray:
    """One pass over the field into its padded blocks, then the padding
    written in place, axis by axis (as ``np.pad(mode="edge")`` pads)."""
    out = np.empty((int(np.prod(nb)), bs, bs, bs), field.dtype)
    view = _block_view(out, nb)
    for vi, fi, split in _copies(field.shape, bs):
        view[vi] = field[fi].reshape(split)
    with trace.span("pad", elements=int(out.size), pad_elements=npad):
        for ax, (n, b) in enumerate(zip(field.shape, nb)):
            last = n - (b - 1) * bs          # real points in the edge block
            if last == bs:
                continue
            lead = (slice(None),) * (2 * ax)
            view[lead + (b - 1, slice(last, None))] = \
                view[lead + (b - 1, slice(last - 1, last))]
    return out


def unblockify(blocks, shape: tuple[int, int, int]):
    """(n_blocks, bs, bs, bs) -> (nx, ny, nz); inverse of :func:`blockify`,
    edge blocks cropped to the field."""
    bs = blocks.shape[-1]
    nx, ny, nz = shape
    bx, by, bz = num_blocks((nx, ny, nz), bs)
    xp = jnp if isinstance(blocks, jnp.ndarray) else np
    whole = (bx * bs, by * bs, bz * bs) == (nx, ny, nz)
    if whole or xp is jnp:
        f = blocks.reshape(bx, by, bz, bs, bs, bs)
        f = xp.transpose(f, (0, 3, 1, 4, 2, 5))
        f = f.reshape(bx * bs, by * bs, bz * bs)
        return f if whole else f[:nx, :ny, :nz]
    blocks = np.asarray(blocks)
    out = np.empty((nx, ny, nz), blocks.dtype)
    view = _block_view(blocks, (bx, by, bz))
    for vi, fi, split in _copies((nx, ny, nz), bs):
        _split(out[fi], split)[...] = view[vi]
    return out
