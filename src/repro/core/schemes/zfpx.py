"""ZFP-style fixed-accuracy scheme: 4^3 cells, block-floating-point + lifting.

Byte layout per chunk: per-cell exponents (i8) followed by the shuffled
quantized-coefficient stream (i32).

``spec.device="jax"`` routes encode/decode through the fused Pallas kernels
(``repro.kernels.ops.zfpx_*``).  The kernel's integer streams are bit-equal
to the host reference, so device- and host-written containers are mutually
bit-exact to decode.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .. import zfpx as _zfp
from . import (Scheme, pack_payload, register_scheme, route, to_device,
               to_host, unshuffle_bytes)


@register_scheme
class ZfpxScheme(Scheme):
    name = "zfpx"
    device_capable = True

    #: conformance contract: the eps-derived bit-plane truncation keeps the
    #: per-cell quantization error within a small multiple of eps (block
    #: floating point + lifting gain), verified by the conformance suite.
    BOUND_FACTOR = 16.0

    def validate(self, spec) -> None:
        if spec.block_size % 4:
            raise ValueError("zfpx needs block_size % 4 == 0")

    def params(self, spec) -> dict:
        return {"eps": spec.eps, **super().params(spec)}

    def error_bound(self, spec) -> float:
        return self.BOUND_FACTOR * spec.eps

    def stage1(self, blocks_np, spec):
        x = to_device(blocks_np, jnp.float32)
        emax, q = route(spec, _zfp.encode, "zfpx_encode")(x, eps=spec.eps)
        emax, q = to_host(emax, q)
        return {"emax": emax, "q": q}

    def serialize(self, s1, lo, hi, spec) -> bytes:
        emax = np.clip(s1["emax"][lo:hi], -127, 127).astype(np.int8)
        q = s1["q"][lo:hi].astype(np.int32, copy=False)
        return pack_payload([emax], q, spec.shuffle, 4)

    def deserialize(self, payload, nblk, spec):
        n = spec.block_size
        nc = (n // 4) ** 3
        emax = np.frombuffer(payload[: nblk * nc], np.int8).astype(np.int32)
        q = np.frombuffer(
            unshuffle_bytes(payload[nblk * nc :], spec.shuffle, 4), np.int32
        )
        emax = emax.reshape(nblk, nc)
        q = q.reshape(nblk, nc, 64)
        dec = route(spec, _zfp.decode, "zfpx_decode")
        return np.asarray(dec(jnp.asarray(emax), jnp.asarray(q),
                              eps=spec.eps, n=n))
