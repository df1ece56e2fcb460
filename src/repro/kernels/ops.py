"""Jit'd public wrappers for the Pallas kernels, instrumented per call.

``interpret=None`` auto-selects from the default backend: real Pallas
lowering on ``tpu``, interpret mode on ``cpu`` (where the tests run; it
executes the kernel body faithfully for correctness validation), and an
error on any other backend — a process that meant to use a TPU and came up
elsewhere must not run the kernels interpreted without saying so.

Every wrapper is wrapped in device-tier observability: first call per
argument signature (shapes/dtypes + static values — the same key ``jax.jit``
compiles on) is a **compile**, later calls are steady-state **execute**, and
the two phases get separate span names (``kernel.compile`` /
``kernel.execute``) and separate ``cz_kernel_seconds`` series — a
compilation stall and a slow steady-state kernel are different problems and
must not share a histogram.

Timing is synchronized (``jax.block_until_ready``) only when someone is
looking: on first-call compiles (jit compilation is host-synchronous
anyway), while the process tracer is enabled, or inside a collecting
request context (the serve tier's tail sampling) — then async dispatch
can't flatter the numbers.  Otherwise the wrapper records dispatch time
only and returns the unforced value, preserving JAX's async-dispatch
pipelining on accelerator backends.  ``CZ_KERNEL_SYNC=1``/``0`` in the
environment (or assigning :data:`SYNC`) forces the choice either way.
"""
from __future__ import annotations

import functools
import os
import threading
import time

import jax

from repro import obs
from repro.obs import context as _context
from repro.obs import trace

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
_SECONDS = obs.histogram(
    "cz_kernel_seconds",
    "Kernel wall time split by compile/execute phase (block_until_ready "
    "on compiles and while tracing/tail collection is active; async "
    "dispatch time otherwise).",
    buckets=obs.FAST_BUCKETS, labelnames=("kernel", "device", "phase"))

#: tri-state host-device sync override for kernel timing: ``True`` forces
#: ``block_until_ready`` on every call, ``False`` never blocks, ``None``
#: (default) blocks only when the timing is observable — first-call
#: compile, process tracer enabled, or a collecting request context.
#: Seeded from ``CZ_KERNEL_SYNC`` when set.
SYNC: bool | None = (None if "CZ_KERNEL_SYNC" not in os.environ
                     else os.environ["CZ_KERNEL_SYNC"].lower()
                     not in ("0", "false", ""))


def _sig(x):
    """One argument's contribution to the compile key — shape/dtype for
    arrays (tracing abstracts values away), the value itself for statics."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype))
    return ("val", x)


def _instrument(name: str):
    """Wrap one jitted kernel with compile/execute phase detection, spans,
    and the ``cz_kernel_*`` metrics.

    Phase detection mirrors ``jax.jit``'s cache key (argument
    shapes/dtypes + static values) with a per-wrapper seen-set: the first
    call for a signature is ``compile``, the rest ``execute``.  An
    approximation — jit cache eviction can recompile a "seen" signature —
    but right for the question the metrics answer: how much wall time is
    warm-up vs steady state.
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
            phase = "compile" if first else "execute"
            sync = SYNC
            if sync is None:
                # block only when the timing is observable: compiles are
                # host-synchronous anyway, and an active tracer/collecting
                # request context needs honest span durations; steady-state
                # uninstrumented calls keep async dispatch pipelining
                ctx = _context.current()
                sync = (first or trace.tracing()
                        or (ctx is not None and ctx.collecting))
            t0 = time.perf_counter_ns()
            out = fn(*a, **k)
            if sync:
                out = jax.block_until_ready(out)
            t1 = time.perf_counter_ns()
            if first:
                _COMPILES.inc(kernel=name, device=device)
            _CALLS.inc(kernel=name, device=device)
            _SECONDS.observe((t1 - t0) / 1e9, kernel=name, device=device,
                             phase=phase)
            trace.record(f"kernel.{phase}", t0, t1, kernel=name,
                         device=device)
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
