"""Wavelet + threshold scheme (the paper's flagship compressor).

Stage 1: 3D wavelet transform per block, significance mask at |c| >= eps,
optional Z4/Z8 low-bit zeroing of detail coefficients.  Byte layout per
chunk: per-block detail counts (u32), packed significance bitmask, then the
coarse corner + significant details as one shuffled float32 stream.

``spec.device="jax"`` routes the forward/inverse transforms through the
batched Pallas kernels (``repro.kernels.ops.wavelet_*`` — whole block batch
in one jitted call); byte layout is unchanged, so device- and host-written
containers interdecode within the declared error bound (the kernel differs
from the host transform only by fp rounding).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .. import shuffle as shuf
from .. import threshold, wavelets
from . import (Scheme, pack_payload, register_scheme, route, to_device,
               to_host, unshuffle_bytes)


@register_scheme
class WaveletScheme(Scheme):
    name = "wavelet"
    device_capable = True

    #: conformance contract: |x - xhat| <= BOUND_FACTOR * eps.  Thresholding
    #: at |c| < eps amplifies through the synthesis stencils across levels;
    #: the factor covers the paper's wavelets at any block size plus the fp
    #: difference between host and Pallas transforms at moderate amplitudes.
    BOUND_FACTOR = 100.0

    def validate(self, spec) -> None:
        if spec.wavelet not in wavelets.WAVELETS:
            raise ValueError(f"unknown wavelet {spec.wavelet}")

    def params(self, spec) -> dict:
        return {"wavelet": spec.wavelet, "eps": spec.eps,
                "levels": spec.levels, "zero_bits": spec.zero_bits,
                **super().params(spec)}

    def error_bound(self, spec) -> float:
        return self.BOUND_FACTOR * spec.eps

    def stage1(self, blocks_np, spec):
        x = to_device(blocks_np, jnp.float32)
        n = spec.block_size
        fwd = route(spec, wavelets.forward3d, "wavelet_forward")
        coeffs = fwd(x, kind=spec.wavelet, levels=spec.levels)
        mask = threshold.significant_mask(coeffs, spec.eps, spec.levels)
        c = wavelets.coarse_side(n, spec.levels)
        mask, coeffs, coarse = to_host(mask, coeffs, coeffs[..., :c, :c, :c])
        return {"mask": mask, "coeffs": coeffs, "coarse": coarse}

    def serialize(self, s1, lo, hi, spec) -> bytes:
        mask = s1["mask"][lo:hi]
        coeffs = s1["coeffs"][lo:hi]
        coarse = s1["coarse"][lo:hi].astype(np.float32)
        details = coeffs[mask].astype(np.float32, copy=False)
        if spec.zero_bits:
            details = shuf.zero_low_bits_np(details, spec.zero_bits)
        counts = mask.reshape(mask.shape[0], -1).sum(-1).astype(np.uint32)
        values = np.concatenate([coarse.reshape(-1), details])
        return pack_payload([counts, np.packbits(mask.reshape(-1))], values,
                            spec.shuffle, 4)

    def deserialize(self, payload, nblk, spec):
        n = spec.block_size
        c = wavelets.coarse_side(n, spec.levels)
        off = 4 * nblk  # skip per-block counts (redundant with the mask)
        mask_bytes = nblk * n * n * n // 8
        mask = np.unpackbits(np.frombuffer(payload[off : off + mask_bytes], np.uint8))
        mask = mask[: nblk * n * n * n].astype(bool).reshape(nblk, n, n, n)
        off += mask_bytes
        values = np.frombuffer(
            unshuffle_bytes(payload[off:], spec.shuffle, 4), np.float32
        )
        ncoarse = nblk * c * c * c
        coarse = values[:ncoarse].reshape(nblk, c, c, c)
        details = values[ncoarse:]
        coeffs = np.zeros((nblk, n, n, n), np.float32)
        coeffs[mask] = details
        coeffs[:, :c, :c, :c] = coarse
        inv = route(spec, wavelets.inverse3d, "wavelet_inverse")
        return np.asarray(inv(jnp.asarray(coeffs), kind=spec.wavelet,
                              levels=spec.levels))
