"""Jit'd public wrappers for the Pallas kernels, instrumented per call.

``interpret=None`` auto-selects from the default backend: real Pallas
lowering on ``tpu``, interpret mode on ``cpu`` (where the tests run; it
executes the kernel body faithfully for correctness validation), and an
error on any other backend — a process that meant to use a TPU and came up
elsewhere must not run the kernels interpreted without saying so.

Every wrapper counts its calls (``cz_kernel_calls_total``) and its first
call per argument signature (shapes/dtypes + static values — the same key
``jax.jit`` compiles on) as a compile (``cz_kernel_compiles_total``).  The
wrappers time nothing and never block: they return the unforced array, so
JAX's async dispatch pipelines on accelerator backends whether or not
anyone is tracing.  A kernel's device time is the profiler trace's (the
``XLA Modules`` line names each ``jit_<kernel>`` program).
"""
from __future__ import annotations

import functools
import threading

import jax

from repro import obs

from .lorenzo import lorenzo_decode_pallas, lorenzo_encode_pallas
from .wavelet3d import wavelet3d_forward, wavelet3d_inverse
from .zfp_transform import zfpx_decode_pallas, zfpx_encode_pallas

__all__ = [
    "wavelet_forward",
    "wavelet_inverse",
    "zfpx_encode",
    "zfpx_decode",
    "lorenzo_encode",
    "lorenzo_decode",
]

_COMPILES = obs.counter(
    "cz_kernel_compiles_total",
    "Kernel calls that hit jit compilation (first call per signature).",
    labelnames=("kernel", "device"))
_CALLS = obs.counter(
    "cz_kernel_calls_total", "Kernel wrapper calls.",
    labelnames=("kernel", "device"))


def _sig(x):
    """One argument's contribution to the compile key — shape/dtype for
    arrays (tracing abstracts values away), the value itself for statics."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype))
    return ("val", x)


def _instrument(name: str):
    """Wrap one jitted kernel with the ``cz_kernel_*`` counters.

    The first call for a signature counts as a compile, through a
    per-wrapper seen-set that mirrors ``jax.jit``'s cache key (argument
    shapes/dtypes + static values).  An approximation — jit cache eviction
    can recompile a "seen" signature — but right for the question the
    counter answers: how many calls paid for warm-up.
    """

    def deco(fn):
        seen: set = set()
        lock = threading.Lock()

        @functools.wraps(fn)
        def wrapper(*a, **k):
            key = (tuple(_sig(x) for x in a),
                   tuple(sorted((kk, _sig(v)) for kk, v in k.items())))
            with lock:
                first = key not in seen
                if first:
                    seen.add(key)
            device = jax.default_backend()
            out = fn(*a, **k)
            if first:
                _COMPILES.inc(kernel=name, device=device)
            _CALLS.inc(kernel=name, device=device)
            return out

        return wrapper

    return deco


def _interp(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels lower for 'tpu' and run interpreted on 'cpu'; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


@_instrument("wavelet_forward")
@functools.partial(jax.jit, static_argnames=("kind", "levels", "interpret"))
def wavelet_forward(blocks, kind: str = "w3ai", levels: int | None = None,
                    interpret: bool | None = None):
    return wavelet3d_forward(blocks, kind, levels, interpret=_interp(interpret))


@_instrument("wavelet_inverse")
@functools.partial(jax.jit, static_argnames=("kind", "levels", "interpret"))
def wavelet_inverse(blocks, kind: str = "w3ai", levels: int | None = None,
                    interpret: bool | None = None):
    return wavelet3d_inverse(blocks, kind, levels, interpret=_interp(interpret))


@_instrument("zfpx_encode")
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def zfpx_encode(blocks, eps: float = 1e-3, interpret: bool | None = None):
    return zfpx_encode_pallas(blocks, eps, interpret=_interp(interpret))


@_instrument("zfpx_decode")
@functools.partial(jax.jit, static_argnames=("eps", "n", "interpret"))
def zfpx_decode(emax, q, eps: float = 1e-3, n: int = 32,
                interpret: bool | None = None):
    return zfpx_decode_pallas(emax, q, eps, n, interpret=_interp(interpret))


@_instrument("lorenzo_encode")
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def lorenzo_encode(blocks, eps: float = 1e-3, interpret: bool | None = None):
    return lorenzo_encode_pallas(blocks, eps, interpret=_interp(interpret))


@_instrument("lorenzo_decode")
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def lorenzo_decode(residuals, eps: float = 1e-3, interpret: bool | None = None):
    return lorenzo_decode_pallas(residuals, eps, interpret=_interp(interpret))
