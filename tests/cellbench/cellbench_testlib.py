"""Shared helpers of the cell-benchmark tests: put the checkout's root and
``src/`` on the path, and run a cell through the harness at a small size on
the CPU (Pallas kernels in interpret mode)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: a 64^3 field is 8 blocks of 32^3; boxes shrink with it, and the in-situ
#: cycle of dumps shrinks to what a window of a second or two holds here
SMALL = {"side": 64}
SMALL_MIX = {"serve_zipf_boxes": {"box_sides": [8, 16, 32],
                                  "sample_share": 0.2},
             **{w: {"cycle_dumps": 2}
                for w in ("insitu_wavelet", "insitu_zfpx", "insitu_lorenzo")}}
SEED = 2 ** 31 + 12345


def run_small(workload: str, seconds: float = 1.0, trace: bool = False,
              control: bool = False, workdir=None, seed: int = SEED,
              config: dict | None = None, mix: dict | None = None,
              **kw) -> dict:
    from cellbench import harness

    return harness.execute(workload, seed, seconds, trace, time.perf_counter(),
                           str(workdir), config_override={**SMALL, **(config or {})},
                           traffic_override={**SMALL_MIX.get(workload, {}),
                                             **(mix or {})},
                           control=control, **kw)
