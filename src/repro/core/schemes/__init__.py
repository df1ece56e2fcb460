"""Open codec-scheme registry (the pluggable substage-1 layer).

The paper's framework is a *testbed of comparison*: wavelet, ZFP-, SZ- and
FPZIP-style compressors plug interchangeably into one block-structured
pipeline.  This package makes that pluggability literal, in the spirit of
Zarr's codec registry: each scheme is a self-describing object that owns

  * ``validate(spec)``  — scheme-specific spec checks,
  * ``stage1(blocks, spec)`` — the device (jit/Pallas) transform over a whole
    block batch, returning named numpy streams,
  * ``serialize(s1, lo, hi, spec)`` / ``deserialize(payload, nblk, spec)`` —
    the host byte layout of one aggregation-buffer chunk (stage-2 lossless
    coding is applied *outside*, by :class:`repro.core.pipeline.Pipeline`).

Third-party schemes register with :func:`register_scheme` and immediately
work through ``Pipeline``, the CZ2 container and the CLI — no core edits.
``SCHEMES`` is a live, read-only view of the registry (iterates names).
"""
from __future__ import annotations

import abc
from collections.abc import Mapping
from typing import TYPE_CHECKING

import numpy as np

from .. import shuffle as _shuf
from ._device import (  # noqa: F401  (re-export)
    DEVICES,
    DeviceFallbackWarning,
    check_device,
    resolve_ops,
    resolved_device,
    route,
    to_device,
    to_host,
)

if TYPE_CHECKING:  # avoid a runtime cycle with repro.core.pipeline
    from ..pipeline import CompressionSpec

__all__ = ["Scheme", "SCHEMES", "register_scheme", "unregister_scheme",
           "get_scheme", "pack_payload", "shuffle_bytes", "unshuffle_bytes",
           "DEVICES", "DeviceFallbackWarning", "check_device", "resolve_ops",
           "resolved_device", "route", "to_device", "to_host"]

_REGISTRY: dict[str, "Scheme"] = {}


def shuffle_bytes(buf: bytes, mode: str, itemsize: int) -> bytes:
    """Optional byte/bit transpose of a value stream (improves stage 2 CR)."""
    if mode == "none" or itemsize == 1:
        return buf
    fn = _shuf.byte_shuffle if mode == "byte" else _shuf.bit_shuffle
    return fn(buf, itemsize)


def pack_payload(head, stream, mode: str, itemsize: int) -> bytearray:
    """One chunk's payload, written once into one buffer: the bytes of each
    array in ``head`` in order, then ``stream``'s bytes shuffled by ``mode``.

    The same bytes as concatenating ``tobytes()`` of each part and
    :func:`shuffle_bytes` of the stream, without those whole-chunk copies;
    the result goes to stage 2 as it is (any buffer will do there).
    """
    parts = [_shuf.byte_view(h) for h in head]
    body = _shuf.byte_view(stream)
    out = bytearray(sum(p.size for p in parts) + body.size)
    view = np.frombuffer(out, np.uint8)
    off = 0
    for p in parts:
        view[off:off + p.size] = p
        off += p.size
    if mode == "byte" and itemsize > 1:
        _shuf.byte_shuffle(body, itemsize, out=view[off:])
    else:
        view[off:] = _shuf.byte_view(shuffle_bytes(body, mode, itemsize))
    return out


def unshuffle_bytes(buf: bytes, mode: str, itemsize: int) -> bytes:
    if mode == "none" or itemsize == 1:
        return buf
    fn = _shuf.byte_unshuffle if mode == "byte" else _shuf.bit_unshuffle
    return fn(buf, itemsize)


class Scheme(abc.ABC):
    """One substage-1 compressor: device transform + host byte layout."""

    #: registry key; also recorded in CZ2 headers
    name: str = ""

    #: whether this scheme has a kernel-backed stage 1 (``device="jax"``
    #: routes through ``repro.kernels.ops``); host-only schemes accept the
    #: knob but truthfully record ``device="host"`` in headers
    device_capable: bool = False

    def validate(self, spec: "CompressionSpec") -> None:
        """Raise ValueError if ``spec`` is invalid for this scheme."""

    def params(self, spec: "CompressionSpec") -> dict:
        """Scheme-relevant knobs, recorded explicitly in container headers.

        ``device`` is always recorded (provenance of where stage 1 *ran*,
        not what the knob asked for — a host-only scheme or a Pallas-less
        fallback reports "host") but is never *required* to decode — see
        ``schemes._device``.
        """
        p = dict(spec.extra) if spec.extra else {}
        # the resolved value wins over any extra key of the same name
        p["device"] = resolved_device(spec, self.device_capable)
        return p

    def error_bound(self, spec: "CompressionSpec") -> float | None:
        """Declared max-abs-error contract for this spec, used by the
        cross-scheme conformance suite (``tests/test_scheme_conformance.py``):

        * ``None``    — lossless: decode must be bit-exact;
        * a float     — decode must satisfy ``max|x - xhat| <= bound``;
        * ``math.inf``— lossy with no declared bound (best effort).
        """
        return None

    def decode_spec(self, spec: "CompressionSpec", fmt: int) -> "CompressionSpec":
        """Spec to decode a payload written under container format ``fmt``.

        Lets a scheme change its byte layout across format bumps while old
        containers keep reading bit-exact (see szx's outlier shuffle in v2).
        """
        return spec

    def chunk_record(self, s1: dict, lo: int, hi: int,
                     spec: "CompressionSpec") -> dict | None:
        """Optional JSON-able per-chunk footer record for blocks [lo, hi),
        called right after :meth:`serialize` for the same range.

        ``None`` (the default) records nothing — containers stay
        byte-identical.  A scheme that varies per chunk (the ``auto``
        meta-scheme records each chunk's winning scheme + eps) returns a
        dict; the container writer collects them into the footer's
        ``chunk_schemes`` table so inspection tooling can describe the
        chunk mix without decoding.
        """
        return None

    @abc.abstractmethod
    def stage1(self, blocks_np: np.ndarray, spec: "CompressionSpec") -> dict[str, np.ndarray]:
        """Device transform of a whole (nblk, bs, bs, bs) batch -> streams."""

    @abc.abstractmethod
    def serialize(self, s1: dict, lo: int, hi: int, spec: "CompressionSpec") -> bytes:
        """Byte layout of blocks [lo, hi) from the stage-1 streams (bytes,
        or a ``bytearray`` such as :func:`pack_payload` writes)."""

    @abc.abstractmethod
    def deserialize(self, payload: bytes, nblk: int, spec: "CompressionSpec") -> np.ndarray:
        """Inverse of :meth:`serialize`: payload -> (nblk, bs, bs, bs) blocks."""


def register_scheme(cls: type) -> type:
    """Class decorator: instantiate and add to the live registry."""
    inst = cls()
    if not inst.name:
        raise ValueError(f"{cls.__name__} must set a non-empty .name")
    _REGISTRY[inst.name] = inst
    return cls


def unregister_scheme(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_scheme(name: str) -> Scheme:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scheme {name!r}; registered: {', '.join(sorted(_REGISTRY))}"
        ) from None


class _SchemesView(Mapping):
    """Live, read-only view of the registry.  Iterates scheme names, so both
    ``"wavelet" in SCHEMES`` and ``for name in SCHEMES`` keep working."""

    def __getitem__(self, name: str) -> Scheme:
        return get_scheme(name)

    def __iter__(self):
        return iter(_REGISTRY)

    def __len__(self) -> int:
        return len(_REGISTRY)

    def __contains__(self, name) -> bool:
        return name in _REGISTRY

    def __repr__(self) -> str:
        return f"SCHEMES({', '.join(sorted(_REGISTRY))})"


SCHEMES = _SchemesView()

# Built-in schemes self-register on import.  ``auto`` comes last: the
# meta-scheme delegates to whatever else is registered.
from . import fpzipx, lorenzo, raw, szx, wavelet, zfpx  # noqa: E402,F401
from . import auto  # noqa: E402,F401
