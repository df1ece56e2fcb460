"""Worker-process environment setup for the cluster engine.

N rank processes each spinning up a multi-threaded BLAS/OpenMP pool
oversubscribes the node and can make the parallel path *slower* than serial
— one compute thread per rank is the paper's model anyway.  The caps must be
in the environment **before** the worker process loads numpy (OpenBLAS/OMP
size their pools at library load) — too early for any in-worker initializer,
since unpickling one already imports the package.  So the parent exports the
caps around spawn-pool creation (:func:`worker_env`); the children inherit
them at exec.

XLA's own CPU threading is left as the parent has it: single-threaded
Eigen (``--xla_cpu_multi_thread_eigen=false``) picks other matmul kernels,
whose float rounding differs in the last bit, and a worker's bytes must
equal the serial writer's.

The workers are also pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
overriding the parent's setting): an accelerator belongs to one process,
and on a machine with a TPU the parent may already hold it, so a worker
that tried for it would fail or hang.
"""
from __future__ import annotations

import contextlib
import os

_THREAD_VARS = ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                "MKL_NUM_THREADS", "NUMEXPR_NUM_THREADS")


@contextlib.contextmanager
def worker_env():
    """Temporarily export per-worker thread caps (explicit settings win)
    and ``JAX_PLATFORMS=cpu``; restores the parent's environment on exit."""
    saved: dict[str, str | None] = {}

    def _set(var: str, val: str) -> None:
        saved[var] = os.environ.get(var)
        os.environ[var] = val

    for var in _THREAD_VARS:
        if var not in os.environ:
            _set(var, "1")
    _set("JAX_PLATFORMS", "cpu")
    try:
        yield
    finally:
        for var, old in saved.items():
            if old is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = old
