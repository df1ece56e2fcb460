"""Device routing for scheme stage-1 transforms (the ``device=`` knob).

``CompressionSpec.device`` selects where a scheme's substage-1 transform
runs:

* ``"host"`` (default) — the pure ``jax.numpy`` reference math in
  ``repro.core`` (wavelets/zfpx/szx), exactly the pre-device code path;
* ``"jax"`` — the jit'd Pallas kernel wrappers in ``repro.kernels.ops``
  (real Pallas lowering on TPU, interpret mode elsewhere).  The whole block
  batch is transformed in one jitted call before chunking.

The knob is a *routing* choice, never a format choice: ``device`` is
recorded in container headers for provenance but is not required to decode.
A file written with ``device="jax"`` decodes bit-exact on host for schemes
whose kernels are integer-exact (zfpx, lorenzo) and within the scheme's
declared error bound otherwise (wavelet — fp rounding only).  When the
installed JAX has no Pallas at all, ``device="jax"`` falls back to host
with a :class:`DeviceFallbackWarning` and counts
``cz_kernel_fallbacks_total``; any other failure to import the kernels
raises.

:func:`to_device` and :func:`to_host` are the copies between host and
device memory that stage 1 and ``CZDataset.append`` make, each in a
``copy.to_device`` / ``copy.to_host`` span that carries the bytes it moved.
"""
from __future__ import annotations

import sys
import warnings

import numpy as np

from repro import obs
from repro.obs import events as _events
from repro.obs import trace

__all__ = ["DEVICES", "DeviceFallbackWarning", "check_device", "kernel_ops",
           "resolve_ops", "route", "resolved_device", "to_device", "to_host"]

_FALLBACKS = obs.counter(
    "cz_kernel_fallbacks_total",
    "device='jax' requests that fell back to the host path "
    "(Pallas toolchain unavailable).")

#: devices a spec may name (recorded in CZ2 headers, validated everywhere)
DEVICES = ("host", "jax")

_UNSET = object()
_OPS = _UNSET


class DeviceFallbackWarning(UserWarning):
    """``device="jax"`` was requested but the Pallas kernel wrappers could
    not be imported; stage 1 ran on the host reference path instead."""


def check_device(device: str) -> None:
    """Raise ValueError on a device name outside :data:`DEVICES`."""
    if device not in DEVICES:
        raise ValueError(
            f"unknown device {device!r}; one of {DEVICES}")


def kernel_ops():
    """``repro.kernels.ops``, or ``None`` when the installed JAX lacks the
    Pallas toolchain (resolved once and cached — the fallback decision is
    per-process).  A kernel module that fails to import for any other
    reason raises: a broken kernel must not pass for a missing one."""
    global _OPS
    if _OPS is _UNSET:
        try:
            from repro.kernels import ops as _ops
        except ModuleNotFoundError as e:
            if not (e.name or "").startswith("jax"):
                raise
            _ops = None
        _OPS = _ops
    return _OPS


def resolve_ops(spec):
    """Kernel-ops module when ``spec`` routes stage 1 to a device, else None.

    ``None`` means "use the host path" — either because the spec asked for
    it or because this JAX has no Pallas (warned, not raised: decode of
    device-written containers must succeed on any host).
    """
    check_device(spec.device)
    if spec.device != "jax":
        return None
    ops = kernel_ops()
    if ops is None:
        _FALLBACKS.inc()
        _events.event("device.fallback", level="warn", requested="jax",
                      used="host")
        warnings.warn(
            "device='jax' requested but this JAX has no Pallas toolchain; "
            "stage 1 falling back to the host path",
            DeviceFallbackWarning, stacklevel=3)
    return ops


def route(spec, host_fn, ops_name: str):
    """The one device dispatch: the named ``kernels.ops`` wrapper when the
    spec routes to a device (and kernels are importable), else ``host_fn``.
    Kernel wrappers and host references share call signatures, so scheme
    code calls the result unconditionally."""
    ops = resolve_ops(spec)
    return host_fn if ops is None else getattr(ops, ops_name)


def resolved_device(spec, device_capable: bool) -> str:
    """Where stage 1 *actually* runs for this spec — what headers record.

    ``"jax"`` only when the scheme has a kernel path and the kernels import;
    a host-only scheme (or a fallback) truthfully reports ``"host"`` no
    matter what the knob asked for."""
    check_device(spec.device)
    if spec.device == "jax" and device_capable and kernel_ops() is not None:
        return "jax"
    return "host"


def _on_device(x) -> bool:
    jax = sys.modules.get("jax")
    return jax is not None and isinstance(x, jax.Array)


def to_device(x, dtype):
    """``jnp.asarray(x, dtype)``, in a ``copy.to_device`` span where ``x``
    is a host array."""
    import jax.numpy as jnp

    if _on_device(x):
        return jnp.asarray(x, dtype)
    x = np.asarray(x)
    with trace.span("copy.to_device", bytes=int(x.nbytes)):
        return jnp.asarray(x, dtype)


def to_host(*arrays) -> list[np.ndarray]:
    """``np.asarray`` of each array, in one ``copy.to_host`` span whose
    ``bytes`` counts the device arrays among them.  Host arrays pass through
    uncounted, and with no device array there is no span: a field that is
    already on the host is not copied."""
    if not any(_on_device(a) for a in arrays):
        return [np.asarray(a) for a in arrays]
    with trace.span("copy.to_host") as sp:
        out = [np.asarray(a) for a in arrays]
        sp.set(bytes=sum(o.nbytes for a, o in zip(arrays, out)
                         if _on_device(a)))
    return out
