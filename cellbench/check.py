"""The comparison that decides ``correct``.

Each answer the timed path produced (a member read back from the store, a
region a query returned) is compared with its reference, the field the
benchmark itself generated.  Three numbers come out, each against a limit
of its own (``limits/<cell>.json``):

* ``max_err/tol``: the worst absolute error over the declared error bound
  plus one float32 ulp at the field's magnitude, the tolerance the
  program's conformance suite and ``chip_smoke.py`` hold every scheme to
  (copied here).  The configuration states this limit: 1.
* ``rms_err/eps``: the worst root-mean-square error of one answer, in units
  of the configured ``eps``.  It separates a sound run from a field stored
  in a lower precision, which can stay inside a wide declared bound.
* ``mismatches``: answers of the wrong shape, missing or uncommitted, and
  requests that failed.  Exact: limit 0.

The control (``control.py``) puts the reference itself in the program's
place, rounded to bfloat16, the precision below the configuration's
float32.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

NAMES = ("max_err/tol", "rms_err/eps", "mismatches")


def tolerance(bound: float, reference: np.ndarray) -> float:
    """The declared bound plus one float32 ulp at the reference's magnitude."""
    ulp = float(np.spacing(np.float32(np.abs(reference).max())))
    return bound * (1 + 1e-4) + ulp


def bfloat16(reference: np.ndarray) -> np.ndarray:
    """The control's answer: the reference rounded to bfloat16."""
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(reference, jnp.bfloat16).astype(jnp.float32))


@dataclasses.dataclass
class Readings:
    """Worst readings over the answers compared so far."""

    bound: float
    eps: float
    max_err_tol: float = 0.0
    rms_err_eps: float = 0.0
    mismatches: int = 0
    compared: int = 0

    def add(self, answer, reference: np.ndarray) -> None:
        """Compare one answer (``None`` if it never came) with its reference."""
        self.compared += 1
        if answer is None or np.shape(answer) != reference.shape:
            self.mismatches += 1
            return
        diff = np.asarray(answer, np.float64) - reference
        if not np.isfinite(diff).all():
            self.mismatches += 1
            return
        err = float(np.abs(diff).max())
        rms = math.sqrt(float(np.mean(diff * diff)))
        self.max_err_tol = max(self.max_err_tol,
                               err / tolerance(self.bound, reference))
        self.rms_err_eps = max(self.rms_err_eps, rms / self.eps)

    def values(self) -> dict:
        return {"max_err/tol": self.max_err_tol,
                "rms_err/eps": self.rms_err_eps,
                "mismatches": self.mismatches}


def judge(values: dict, limits: dict) -> list[dict]:
    """``[{"name", "value", "limit"}, ...]`` for every limited number; a
    number with no limit is a fault of the limits file, not a pass."""
    out = []
    for name in NAMES:
        if name not in limits:
            raise KeyError(f"no limit for {name!r}")
        out.append({"name": name, "value": values[name],
                     "limit": limits[name]["limit"]})
    return out


def correct(checks: list[dict]) -> bool:
    return bool(checks) and all(
        c["value"] <= c["limit"] for c in checks)
