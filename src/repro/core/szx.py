"""``szx`` — SZ-style error-bounded predictive quantization, TPU-adapted.

SZ (Di & Cappello 2016) predicts each value from *reconstructed* neighbours
(Lorenzo predictor) and quantizes the residual — a serial data dependence.
We adopt the dual-quantization reformulation (the same one cuSZ uses on
GPUs): quantize first onto the 2*eps grid, then take the exact integer 3D
Lorenzo difference:

    q = round(x / (2 eps))           (int32)
    r = (I - Sx)(I - Sy)(I - Sz) q   (three axis-wise finite differences)

Encoding is three parallel diffs; decoding is three parallel inclusive
prefix sums (cumsum — TPU native).  The error bound |x - xhat| <= eps holds
*exactly*, like SZ's.  Residuals concentrate near zero and are entropy-coded
by the host stage 2 (int8 stream with escape marker + outlier list + ZLIB).

Prediction is block-local (CubismZ block independence): each (bs,bs,bs)
block is differenced independently, so blocks decompress in isolation.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["encode", "decode", "grid_scales", "lorenzo_fwd", "lorenzo_inv",
           "max_eps_ratio"]

# |q| must fit int32 with headroom for the 3D diff (factor <= 8).
_Q_LIMIT = 2 ** 27


def max_eps_ratio() -> float:
    """Smallest allowed eps relative to max|x|: eps >= max|x| / (2*_Q_LIMIT)."""
    return 1.0 / (2.0 * _Q_LIMIT)


def lorenzo_fwd(q):
    """3D Lorenzo residual over trailing three axes (exact int arithmetic)."""
    for ax in (-3, -2, -1):
        q = jnp.diff(q, axis=ax, prepend=jnp.zeros_like(jnp.take(q, jnp.asarray([0]), axis=ax)))
    return q


def lorenzo_inv(r):
    """Inverse: inclusive cumsum along each axis (wrapping int arithmetic)."""
    for ax in (-1, -2, -3):
        r = jnp.cumsum(r, axis=ax, dtype=r.dtype)
    return r


def grid_scales(eps: float) -> np.ndarray:
    """``[1 / (2 eps), 2 eps]``, the quantization grid's constants, as the
    float32 operand that encode and decode (and the Lorenzo kernels) take.
    Worked out in double and rounded once, so every ``eps`` reaches the
    device as the same float32 constants, and one compiled program serves
    every ``eps``."""
    return np.array([1.0 / (2.0 * eps), 2.0 * eps], np.float32)


def encode(blocks, eps: float = 1e-3):
    """blocks (B, n, n, n) float32 -> int32 Lorenzo residuals (B, n, n, n).

    Quantization uses a compensated two-step refinement: fp32 rounding of
    ``x / 2eps`` can shift the rounding decision when |q| is large, so after
    the first round we re-quantize the reconstruction residual.  This keeps
    |x - q*2eps| <= eps up to one ulp of x (tested with hypothesis).
    """
    return _encode(blocks, grid_scales(eps))


@jax.jit
def _encode(blocks, scales):
    x = jnp.asarray(blocks, jnp.float32)
    inv, step = scales[0], scales[1]
    q = jnp.round(x * inv)
    err = x - q * step
    q = (q + jnp.round(err * inv)).astype(jnp.int32)
    return lorenzo_fwd(q)


def decode(residuals, eps: float = 1e-3):
    return _decode(residuals, grid_scales(eps))


@jax.jit
def _decode(residuals, scales):
    q = lorenzo_inv(residuals)
    return q.astype(jnp.float32) * scales[1]


def check_eps(fields_absmax: float, eps: float) -> None:
    if eps <= 0:
        raise ValueError("szx requires eps > 0 (error-bounded lossy codec)")
    if fields_absmax / (2.0 * eps) >= _Q_LIMIT:
        raise ValueError(
            f"eps={eps} too small for data with max|x|={fields_absmax}: "
            f"quantized values would overflow int32 (need eps >= "
            f"{fields_absmax * max_eps_ratio():.3e})"
        )
