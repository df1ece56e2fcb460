"""Run one benchmark cell once and print its result line.

    python -m cellbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit;
the checks are also the last lines of standard error.

The run fails, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the repository's ``src/`` is not in the
checkout.  JAX's persistent compilation cache lives at ``.jax_cache/`` in
the checkout, so only a cell's first run there compiles.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from cellbench import registry  # noqa: E402

CACHE_DIR = registry.ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python -m cellbench.run",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int) -> None:
    # the TPU runtime logs under /tmp unless told otherwise: keep it in
    # this run's own temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")


def use_checkout_cache() -> None:
    import jax

    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    src = registry.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"cellbench: the program is not in this checkout ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        require_chips(int(cell["chips"]))
        print(f"setup devices {time.perf_counter() - T0:.3f} s",
              file=sys.stderr)
    except NoChip as e:
        print(f"cellbench: {e}", file=sys.stderr)
        return 3
    use_checkout_cache()

    from cellbench import harness

    with tempfile.TemporaryDirectory(prefix="cellbench-") as work:
        result = harness.execute(args.workload, args.seed, args.seconds,
                                 bool(args.trace), T0, work, bench=bench)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
