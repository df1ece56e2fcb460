"""Lorenzo-predictor scheme: dual-quantized 3D Lorenzo residuals, i32 stream.

The predictor-based arm of the registry (Tao et al. 2017's Lorenzo family):
stage 1 quantizes onto the 2*eps grid and takes the exact integer 3D Lorenzo
difference — the same transform ``szx`` uses — but the byte layout keeps the
full int32 residual stream (shuffled, then stage-2 coded) instead of szx's
int8+escape coding.  That trades raw stream size for a branch-free layout
whose serialize/deserialize is pure ``tobytes``/``frombuffer``, and leaves
entropy coding entirely to the shuffle + stage-2 combination.

``spec.device="jax"`` routes encode/decode through the fused Pallas kernels
(``repro.kernels.ops.lorenzo_*`` — quantization fused with the axis diffs /
prefix sums).  The kernels are integer-exact vs the host path, so device-
and host-written containers are mutually bit-exact to decode.  The error
bound |x - xhat| <= eps holds exactly, like SZ's.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .. import szx as _szx
from . import (Scheme, pack_payload, register_scheme, route, to_device,
               to_host, unshuffle_bytes)


@register_scheme
class LorenzoScheme(Scheme):
    name = "lorenzo"
    device_capable = True

    def validate(self, spec) -> None:
        if spec.eps <= 0:
            raise ValueError(
                "lorenzo requires eps > 0 (error-bounded lossy codec)")

    def params(self, spec) -> dict:
        return {"eps": spec.eps, **super().params(spec)}

    def error_bound(self, spec) -> float:
        return spec.eps

    def stage1(self, blocks_np, spec):
        x = to_device(blocks_np, jnp.float32)
        _szx.check_eps(float(jnp.max(jnp.abs(x))), spec.eps)
        res = route(spec, _szx.encode, "lorenzo_encode")(x, eps=spec.eps)
        res, = to_host(res)
        return {"res": res}

    def serialize(self, s1, lo, hi, spec) -> bytes:
        r = s1["res"][lo:hi].astype(np.int32, copy=False)
        return pack_payload([], r, spec.shuffle, 4)

    def deserialize(self, payload, nblk, spec):
        n = spec.block_size
        r = np.frombuffer(unshuffle_bytes(payload, spec.shuffle, 4), np.int32)
        r = r.reshape(nblk, n, n, n)
        dec = route(spec, _szx.decode, "lorenzo_decode")
        return np.asarray(dec(jnp.asarray(r), eps=spec.eps))
