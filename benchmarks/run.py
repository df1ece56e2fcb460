"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness convention) and writes
detailed JSON to artifacts/bench/.  ``--full`` runs the publication-size
sweeps; default is the quick variant (CI-friendly).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated bench names (e.g. methods,speed)")
    args = ap.parse_args(argv)

    from repro.launch.jax_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_backends,
        bench_blocksize,
        bench_ckpt,
        bench_coeff,
        bench_device,
        bench_gradcomp,
        bench_insitu,
        bench_methods,
        bench_parallel,
        bench_scaling,
        bench_serve,
        bench_shuffle,
        bench_speed,
        bench_store,
        bench_tolerance,
        bench_wavelet_time,
        bench_wavelet_types,
    )

    benches = {
        "wavelet_time": bench_wavelet_time,
        "wavelet_types": bench_wavelet_types,
        "shuffle": bench_shuffle,
        "blocksize": bench_blocksize,
        "methods": bench_methods,
        "coeff": bench_coeff,
        "speed": bench_speed,
        "tolerance": bench_tolerance,
        "scaling": bench_scaling,
        "insitu": bench_insitu,
        "ckpt": bench_ckpt,
        "gradcomp": bench_gradcomp,
        "store": bench_store,
        "backends": bench_backends,
        "parallel": bench_parallel,
        "device": bench_device,
        "serve": bench_serve,
    }
    only = [s for s in args.only.split(",") if s]
    unknown = sorted(set(only) - set(benches))
    if unknown:
        # a typo must fail loudly, not let the CI smoke job pass while
        # silently running zero benchmarks
        print(f"# unknown bench name(s): {', '.join(unknown)}; "
              f"known: {', '.join(sorted(benches))}", file=sys.stderr)
        raise SystemExit(2)
    from . import common

    failures = []
    for name, mod in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# === {name} ===", file=sys.stderr)
        try:
            metrics = mod.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            failures.append(name)
        else:
            # one machine-readable record per bench: params + run() return
            # + the full cz_* registry snapshot (perf trajectory across PRs)
            rec = common.write_bench_record(
                name, {"quick": not args.full,
                       "duration_s": round(time.time() - t0, 3)}, metrics)
            print(f"# wrote {rec}", file=sys.stderr)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
