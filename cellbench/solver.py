"""The benchmark's own field producer: a 3D compressible Euler solver.

A copy of the program's ``repro.fields.euler3d`` (finite volume, Rusanov
fluxes, RK2, periodic box), kept here so that the data every cell
compresses and every comparison refers to cannot change with the program.
The initial bubble cloud is built on the device in one jitted call.

The cloud's geometry (bubble count, centres, radii) comes from the
configuration; a seed picks a periodic translation of it.  The solver is
translation-equivariant.  With the configuration's ``placement`` at
``"blocks"`` (every timed run) the translation is by whole blocks of the
configured block size along the first axis; the pipeline's chunks are runs
of blocks in C order, so each chunk holds whole rows of blocks of one
first-axis index, and every seed gives the same chunks in another order,
at every step, and the same work.  With ``"cells"`` (the readings that the
limits of ``correct`` are set from, ``control.py --placement cells``) the
translation is by any number of cells along every axis: every seed puts
other data in every block.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

GAMMA = 1.4


def bubble_geometry(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """Centres (k, 3) and radii (k,) of the configuration's bubble cloud,
    drawn as ``repro.fields.EulerConfig`` draws them."""
    cloud = cfg["cloud"]
    rng = np.random.default_rng(cloud["geometry_seed"])
    centres, radii = [], []
    for _ in range(cloud["n_bubbles"]):
        centres.append(rng.uniform(0.3, 0.7, 3))
        radii.append(rng.uniform(0.04, 0.09))
    return np.asarray(centres), np.asarray(radii)


def placement(seed: int, n: int, block: int, mode: str) -> np.ndarray:
    """Shift (3,) in cells drawn from the seed: ``"blocks"``, whole blocks
    along the first axis and none along the others; ``"cells"``, any cell
    along every axis."""
    rng = np.random.default_rng(seed)
    if mode == "cells":
        return rng.integers(0, n, 3)
    if mode != "blocks":
        raise ValueError(f"unknown placement {mode!r}")
    return np.asarray([int(rng.integers(0, n // block)) * block, 0, 0])


@functools.partial(jax.jit, static_argnames=("n",))
def _initial(centres, radii, consts, shift, n: int):
    p_amb, p_bub, rho_liq, rho_gas = consts[0], consts[1], consts[2], consts[3]
    ax = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
    chi = jnp.zeros((n, n, n), jnp.float32)
    for b in range(centres.shape[0]):
        c = centres[b]
        dx = [jnp.abs(ax - c[i]) for i in range(3)]
        d = jnp.sqrt(dx[0][:, None, None] ** 2 + dx[1][None, :, None] ** 2
                     + dx[2][None, None, :] ** 2)
        chi = jnp.maximum(chi, 0.5 * (1 - jnp.tanh((d - radii[b]) / (1.5 / n))))
    rho = rho_liq * (1 - chi) + rho_gas * chi
    p = p_amb * (1 - chi) + p_bub * chi
    zero = jnp.zeros_like(rho)
    U = jnp.stack([rho, zero, zero, zero, p / (GAMMA - 1)])
    return jnp.roll(U, (shift[0], shift[1], shift[2]), axis=(1, 2, 3))


def initial_state(cfg: dict, seed: int):
    """(5, n, n, n) float32 state [rho, rho*u, rho*v, rho*w, E] on the
    device: the configuration's cloud, translated by the seed."""
    n = int(cfg["side"])
    cloud = cfg["cloud"]
    centres, radii = bubble_geometry(cfg)
    shift = placement(seed, n, int(cfg["block"]), cfg["placement"])
    consts = np.asarray([cloud["p_ambient"], cloud["p_bubble"],
                         cloud["rho_liquid"], cloud["rho_gas"]], np.float32)
    return _initial(jnp.asarray(centres, jnp.float32),
                    jnp.asarray(radii, jnp.float32), jnp.asarray(consts),
                    jnp.asarray(shift, jnp.int32), n=n)


def primitives(U):
    rho = U[0]
    vel = U[1:4] / rho
    ke = 0.5 * rho * jnp.sum(vel ** 2, axis=0)
    p = (GAMMA - 1) * (U[4] - ke)
    return rho, vel, p


def _flux(U, axis: int):
    rho, vel, p = primitives(U)
    un = vel[axis]
    return jnp.stack([
        rho * un,
        U[1] * un + (p if axis == 0 else 0.0),
        U[2] * un + (p if axis == 1 else 0.0),
        U[3] * un + (p if axis == 2 else 0.0),
        (U[4] + p) * un,
    ])


def _rusanov_div(U, dx: float):
    rho, vel, p = primitives(U)
    c = jnp.sqrt(GAMMA * jnp.maximum(p, 1e-8) / rho)
    div = jnp.zeros_like(U)
    for axis in range(3):
        sp = jnp.abs(vel[axis]) + c
        F = _flux(U, axis)
        ax = axis + 1
        Up = jnp.roll(U, -1, axis=ax)
        Fp = jnp.roll(F, -1, axis=ax)
        a = jnp.maximum(sp, jnp.roll(sp, -1, axis=axis))
        hi = 0.5 * (F + Fp) - 0.5 * a[None] * (Up - U)
        div = div + (hi - jnp.roll(hi, 1, axis=ax)) / dx
    return div


@jax.jit
def step(U, dt):
    """One RK2 step of size ``dt``."""
    dx = 1.0 / U.shape[-1]
    k1 = -_rusanov_div(U, dx)
    k2 = -_rusanov_div(U + dt * k1, dx)
    return U + 0.5 * dt * (k1 + k2)


@jax.jit
def _max_speed(U):
    _, vel, p = primitives(U)
    c = jnp.sqrt(GAMMA * jnp.maximum(p, 1e-8) / U[0])
    return jnp.max(jnp.abs(vel) + c[None])


def cfl_dt(U, cfl: float = 0.35) -> float:
    """Fixed step size for a run, from its initial state."""
    return cfl / U.shape[-1] / (3.0 * float(_max_speed(U)))


@jax.jit
def _qois(U):
    rho, _, p = primitives(U)
    return {"p": p, "rho": rho, "E": U[4]}


def qois(U, names) -> dict:
    """The named quantities of interest of a state, on the device."""
    out = _qois(U)
    return {q: out[q] for q in names}
