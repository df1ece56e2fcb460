"""On-chip smoke run of the main path: compress -> store -> decode -> region reads.

    python chip_smoke.py

Run from the root of a checkout on a machine with one TPU.  One process
holds the chip and runs three phases through the entry points a user
calls:

1. in situ: the Euler solver steps a 256^3 bubble cloud on the chip, and
   its ``p``, ``rho`` and ``E`` snapshots are appended to a ``CZDataset``
   once per kernel-backed scheme (``wavelet``, ``zfpx``, ``lorenzo``) with
   ``device="jax"``; every member is read back and held to its scheme's
   declared error bound, and to bit-exactness against the host reference
   where the scheme promises it;
2. ex situ: ``cz-compress`` (``repro.launch.compress.main``, in this
   process) compresses four 256^3 cavitation QoIs, then decompresses each
   with ``--verify-against`` its source field;
3. region reads: a ``FieldRegionServer`` over each in-situ dataset answers
   an interior box, a box across chunk boundaries and the full field.

Each phase prints one JSON line: compression ratio, PSNR, worst absolute
error beside the declared bound, wall and compile seconds, and the kernel
calls it made.  Those timings are smoke output, not benchmark results.  The
last line is ``{"ok": true, "device": {...}}``.  The script exits nonzero,
and prints no such line, when JAX finds no TPU, when the repository's
``src/`` is not beside it, or when any phase or check fails; a kernel that
falls back to the host path or runs interpreted is a failure.
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import os
import re
import sys
import tempfile
import time
import warnings

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

N = 256                   # field side: one chip holds the Euler step at 256^3
STEPS = 4                 # solver steps before the snapshot
BLOCK = 32
EPS = 1e-3
KERNEL_SCHEMES = ("wavelet", "zfpx", "lorenzo")
EXACT_SCHEMES = ("zfpx", "lorenzo")   # device and host decode bit-exact
EXSITU_QOIS = ("p", "rho", "E", "a2")


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def tolerance(spec, field: np.ndarray) -> float:
    """The scheme's declared bound plus one float32 ulp at the field's
    magnitude — the tolerance ``tests/test_scheme_conformance.py`` holds
    every scheme to."""
    from repro.core import get_scheme

    bound = get_scheme(spec.scheme).error_bound(spec)
    ulp = float(np.spacing(np.float32(np.abs(field).max())))
    return bound * (1 + 1e-4) + ulp


def _member(scheme: str, quantity: str, field, dec, nbytes: int, spec) -> dict:
    from repro.core import compression_ratio, get_scheme, psnr

    _check(dec.shape == field.shape and bool(np.isfinite(dec).all()),
           f"{scheme}/{quantity}: decode is not a finite {field.shape} field")
    err = float(np.max(np.abs(dec.astype(np.float64) - field)))
    rec = {"scheme": scheme, "quantity": quantity,
           "ratio": compression_ratio(field.nbytes, nbytes),
           "psnr_db": psnr(field, dec), "max_err": err,
           "bound": get_scheme(scheme).error_bound(spec)}
    _check(err <= tolerance(spec, field),
           f"{scheme}/{quantity}: max error {err!r} above bound {rec['bound']!r}")
    return rec


def insitu(workdir: str, n: int = N, steps: int = STEPS):
    """Phase 1.  Returns (report, fields, dataset roots by scheme)."""
    import jax
    from repro.core import CompressionSpec, container
    from repro.fields import EulerConfig, init_bubble_cloud, primitives, run
    from repro.store import CZDataset

    U = jax.block_until_ready(run(init_bubble_cloud(EulerConfig(n=n)), steps))
    rho, _, p = primitives(U)
    fields = {"p": np.asarray(p), "rho": np.asarray(rho),
              "E": np.asarray(U[4])}
    for q, f in fields.items():
        _check(f.shape == (n, n, n) and bool(np.isfinite(f).all()),
               f"solver field {q} is not a finite {(n, n, n)} array")
    members, roots = [], {}
    for scheme in KERNEL_SCHEMES:
        spec = CompressionSpec(scheme=scheme, eps=EPS, block_size=BLOCK,
                               device="jax")
        roots[scheme] = root = os.path.join(workdir, "insitu", scheme)
        with CZDataset(root, mode="a", spec=spec) as ds:
            t = ds.append(fields, time=float(steps))
            for q, f in fields.items():
                info = ds.timestep_info(q, t)
                dec = ds.read_field(q, t)
                rec = _member(scheme, q, f, dec, info["bytes"], spec)
                if scheme in EXACT_SCHEMES:
                    # the kernels' integer streams equal the reference's:
                    # device and host decodes agree, and so do the decodes
                    # of a device-written and a host-written container
                    host = container.read_field(info["file"], device="host",
                                                store=ds.store)
                    ref = os.path.join(workdir, f"{scheme}-{q}-host.cz")
                    container.write_field(ref, f,
                                          dataclasses.replace(spec, device="host"))
                    rec["bit_exact"] = bool(
                        np.array_equal(host, dec)
                        and np.array_equal(container.read_field(ref), dec))
                    _check(rec["bit_exact"],
                           f"{scheme}/{q}: device and host decodes differ")
                members.append(rec)
    return {"n": n, "steps": steps, "members": members}, fields, roots


def exsitu(workdir: str, n: int = N) -> dict:
    """Phase 2: the ``cz-compress`` entry point, called in this process."""
    from repro.core import CompressionSpec, container
    from repro.fields import CloudConfig, cavitation_fields
    from repro.launch import compress

    out = os.path.join(workdir, "exsitu")
    with contextlib.redirect_stdout(sys.stderr):   # the CLI's own report
        compress.main(["--source", "cavitation", "--n", str(n),
                       "--qoi", ",".join(EXSITU_QOIS), "--scheme", "wavelet",
                       "--eps", str(EPS), "--block-size", str(BLOCK),
                       "--device", "jax", "--out", out])
    with open(os.path.join(out, "report.json")) as f:
        report = json.load(f)
    spec = CompressionSpec.from_json(report["spec"])
    fields = cavitation_fields(CloudConfig(n=n), 9.4)   # the CLI's default --t
    members = []
    for q in EXSITU_QOIS:
        src = os.path.join(workdir, f"{q}.npy")
        np.save(src, fields[q])
        cz = os.path.join(out, f"{q}.cz")
        said = io.StringIO()
        with contextlib.redirect_stdout(said):
            compress.main(["--decompress", cz, "--verify-against", src])
        _check(re.search(r"maxerr \S+", said.getvalue()) is not None,
               f"--verify-against printed no maxerr for {q}")
        rec = report["fields"][q]
        _check(rec["bytes"] == os.path.getsize(cz),
               f"{q}: report.json size disagrees with the file")
        members.append(_member("wavelet", q, fields[q], container.read_field(cz),
                               rec["bytes"], spec))
    return {"n": n, "members": members}


def boxes(n: int) -> dict:
    """An interior box inside one block, a box across block and chunk
    boundaries, and the full field."""
    return {"interior": ((n // 8 + 2,) * 3, (n // 8 + n // 16,) * 3),
            "cross_chunk": ((n // 16, 3 * n // 8, n // 4),
                            (5 * n // 16, 5 * n // 8, 13 * n // 16)),
            "full": ((0, 0, 0), (n, n, n))}


def regions(roots: dict, fields: dict, n: int = N) -> dict:
    """Phase 3: region queries through the serve tier."""
    from repro.core import CompressionSpec, get_scheme
    from repro.serve import FieldRegionServer

    queries = []
    for scheme, root in roots.items():
        spec = CompressionSpec(scheme=scheme, eps=EPS, block_size=BLOCK)
        bound = get_scheme(scheme).error_bound(spec)
        with FieldRegionServer(root) as srv:
            for q, f in fields.items():
                for name, (lo, hi) in boxes(n).items():
                    got = srv.query(q, 0, lo, hi)
                    want = f[tuple(slice(a, b) for a, b in zip(lo, hi))]
                    _check(got.shape == want.shape,
                           f"{scheme}/{q}/{name}: shape {got.shape}")
                    err = float(np.max(np.abs(got.astype(np.float64) - want)))
                    _check(err <= tolerance(spec, f),
                           f"{scheme}/{q}/{name}: error {err!r} above bound")
                    queries.append({"scheme": scheme, "quantity": q,
                                    "box": name, "max_err": err,
                                    "bound": bound})
    return {"n": n, "queries": queries}


def kernel_calls() -> dict:
    """``cz_kernel_calls_total`` as ``{"kernel@device": calls}``."""
    from repro import obs
    from repro.kernels import ops  # noqa: F401  (registers the metric)

    calls = obs.REGISTRY.get("cz_kernel_calls_total")
    return {f"{lbl['kernel']}@{lbl['device']}": int(v)
            for lbl, v in calls.samples()}


def check_kernel_metrics(platform: str) -> dict:
    """Every kernel call ran on ``platform``, none fell back to the host
    path, and the kernels lower for the chip instead of interpreting."""
    from repro import obs
    from repro.kernels import ops

    calls = obs.REGISTRY.get("cz_kernel_calls_total").samples()
    _check(bool(calls), "no kernel call was recorded")
    devices = sorted({lbl["device"] for lbl, _ in calls})
    _check(devices == [platform],
           f"cz_kernel_calls_total devices {devices}, want [{platform!r}]")
    fallbacks = obs.REGISTRY.get("cz_kernel_fallbacks_total").value()
    _check(fallbacks == 0, f"cz_kernel_fallbacks_total is {fallbacks}")
    _check(ops._interp(None) is (platform == "cpu"),
           "kernels would run in interpret mode")
    return {"fallbacks": fallbacks, "devices": devices}


class _CompileClock:
    """Seconds JAX spent in backend compiles (a persistent-cache hit is
    counted at its retrieval time), and the cache hits."""

    def __init__(self):
        import jax

        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _phase(name: str, clock: _CompileClock, fn, *args):
    calls0, c0, h0 = kernel_calls(), clock.seconds, clock.cache_hits
    t0 = time.perf_counter()
    out = fn(*args)
    report = out[0] if isinstance(out, tuple) else out
    calls = kernel_calls()
    report = {"phase": name, **report,
              "wall_s": time.perf_counter() - t0,
              "compile_s": clock.seconds - c0,
              "compile_cache_hits": clock.cache_hits - h0,
              "kernel_calls": {k: v - calls0.get(k, 0) for k, v in calls.items()
                               if v != calls0.get(k, 0)},
              "note": "smoke output, not a benchmark result"}
    print(json.dumps(report), flush=True)
    return out


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 1
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {device})", file=sys.stderr)
        return 1

    from repro.core.schemes import DeviceFallbackWarning
    from repro.launch.jax_cache import enable_compile_cache

    warnings.simplefilter("error", DeviceFallbackWarning)
    cache = enable_compile_cache()
    clock = _CompileClock()
    print(json.dumps({"phase": "setup", "device": device,
                      "compile_cache": cache}), flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke") as work:
        _, fields, roots = _phase("insitu", clock, insitu, work)
        _phase("exsitu", clock, exsitu, work)
        _phase("regions", clock, regions, roots, fields)
    print(json.dumps({"phase": "checks",
                      **check_kernel_metrics(device["platform"]),
                      "kernel_calls": kernel_calls(),
                      "compile_s": clock.seconds,
                      "compile_cache_hits": clock.cache_hits}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
