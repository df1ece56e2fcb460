"""Readings that the limits of ``correct`` are set from, for one cell.

    python -m cellbench.control --workload <cell> --seconds <s> \\
        --seeds <n>... --control-seeds <n>... [--placement cells]

Runs the cell at its own size in one process: once per ``--seeds`` seed as
the program (the sound runs, whose largest reading of each number is the
lower reading), and once per ``--control-seeds`` seed with the reference,
rounded to bfloat16, put in the program's place (whose smallest reading is
the upper one).  ``--placement cells`` moves the cloud by any cell offset
drawn from each seed (``solver.placement``), so that every seed puts other
data in every block, where the timed runs move it by whole blocks only.
Prints one JSON line per run and, last, the lower and upper readings of
each number beside the cell's limits.  The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from cellbench import check, registry, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m cellbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--placement", choices=("blocks", "cells"),
                    help="the cloud's placement (default: the configuration's)")
    args = ap.parse_args(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    sys.path.insert(0, str(registry.ROOT / "src"))
    try:
        run.require_chips(int(cell["chips"]))
    except run.NoChip as e:
        print(f"cellbench.control: {e}", file=sys.stderr)
        return 3
    run.use_checkout_cache()

    from cellbench import harness

    lower = {n: 0.0 for n in check.NAMES}
    upper = {n: float("inf") for n in check.NAMES}
    override = {"placement": args.placement} if args.placement else None
    runs = [(s, False) for s in args.seeds] + \
        [(s, True) for s in args.control_seeds]
    for seed, control in runs:
        with tempfile.TemporaryDirectory(prefix="cellbench-") as work:
            r = harness.execute(args.workload, seed, args.seconds, False,
                                time.perf_counter(), work, bench=bench,
                                config_override=override, control=control)
        values = {n: c["value"] for n, c in r["checks"].items()}
        side = upper if control else lower
        pick = min if control else max
        for n, v in values.items():
            side[n] = pick(side[n], v)
        print(json.dumps({"seed": seed, "control": control,
                          "placement": args.placement,
                          "correct": r["correct"], "readings": values,
                          "attempted": r["attempted"]}), flush=True)
    limits = registry.limits(args.workload)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper,
                      "limits": {n: limits[n]["limit"] for n in check.NAMES}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
