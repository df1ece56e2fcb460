"""The benchmark's stand-in for the SDRBench Hurricane ISABEL snapshots.

SDRBench (Zhao et al., IEEE BigData 2020) ships the NCAR WRF simulation of
hurricane Isabel from the IEEE Visualization 2004 contest: 48 timesteps of
13 float32 quantities, each 100 x 500 x 500 (height, latitude, longitude),
one raw file per quantity and timestep.  The data cannot be fetched here, so
this module fills fields of exactly those names, shape and dtype from a
seed, on the device, as the configuration's ``assumed`` says:

* a vortex whose eye moves with the timestep, its track drawn from the seed;
* signed smooth fields in their physical units: ``U``, ``V`` and ``W`` (m/s,
  a tangential wind that decays with radius and height, a background
  steering flow, an eyewall updraft), ``P`` (perturbation pressure, Pa) and
  ``TC`` (temperature, degrees C, with a lapse rate and a warm core);
* moisture fields (kg/kg) that are non-negative and mostly zero outside
  spiral rainbands: ``QCLOUD``, ``QRAIN``, ``QICE``, ``QSNOW``, ``QGRAUP``,
  their sums ``CLOUD`` and ``PRECIP``, and ``QVAPOR``, which decays with
  height;
* small-scale structure from a few Fourier modes drawn from the seed, so
  that no field is smoother than a simulation's;
* no below-ground fill value (the contest data marks such points with
  1e35; this generator does not reproduce them).

A timestep's fields are made by two jitted calls, the small-scale modes
and then every field, and each field is copied to the host once.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

QUANTITIES = ("CLOUD", "PRECIP", "P", "QCLOUD", "QGRAUP", "QICE", "QRAIN",
              "QSNOW", "QVAPOR", "TC", "U", "V", "W")

#: per moisture species: (peak kg/kg, height of its layer, layer depth,
#: rainband phase offset, share of the domain left dry by the threshold)
_SPECIES = {
    "QCLOUD": (2.0e-3, 0.25, 0.20, 0.0, 0.55),
    "QRAIN": (8.0e-3, 0.15, 0.18, 0.4, 0.60),
    "QICE": (1.5e-3, 0.70, 0.15, 1.1, 0.65),
    "QSNOW": (3.0e-3, 0.60, 0.18, 1.7, 0.62),
    "QGRAUP": (6.0e-3, 0.45, 0.12, 2.3, 0.70),
}
_MODES = 8


def track(seed: int, timesteps: int) -> dict:
    """The run's parameters, drawn from the seed: the eye's track, the
    storm's strength, and the small-scale modes (wave vectors, phases)."""
    rng = np.random.default_rng([seed, 17])
    start = rng.uniform(0.35, 0.65, 2)
    heading = rng.uniform(0, 2 * np.pi)
    step = rng.uniform(0.004, 0.012)
    eyes = np.stack([start + t * step * np.array([np.cos(heading),
                                                  np.sin(heading)])
                     for t in range(timesteps)])
    return {
        "eyes": eyes.astype(np.float32),                       # (T, 2) y, x
        "storm": np.asarray([rng.uniform(55, 70),              # vmax m/s
                             rng.uniform(0.03, 0.05),          # rmax
                             rng.uniform(4000, 5500),          # dp Pa
                             rng.uniform(2.5, 4.0)],           # spiral pitch
                            np.float32),
        "waves": rng.uniform(2, 24, (_MODES, 3)).astype(np.float32),
        "phases": rng.uniform(0, 2 * np.pi, (_MODES,)).astype(np.float32),
        "flow": rng.uniform(-6, 6, 2).astype(np.float32),
    }


def _axes(shape):
    """Cell-centred coordinates in [0, 1] along (height, latitude,
    longitude), shaped to broadcast."""
    nz, ny, nx = shape
    z = (jnp.arange(nz, dtype=jnp.float32) + 0.5)[:, None, None] / nz
    y = (jnp.arange(ny, dtype=jnp.float32) + 0.5)[None, :, None] / ny
    x = (jnp.arange(nx, dtype=jnp.float32) + 0.5)[None, None, :] / nx
    return z, y, x


@functools.partial(jax.jit, static_argnames=("shape",))
def _small(waves, phases, shape):
    """The small-scale structure: the mean of ``_MODES`` plane waves, made
    once and handed to every field.  Unrolled into each of the 13 fields,
    the modes took the TPU compiler 50 s for the full shape."""
    z, y, x = _axes(shape)

    def mode(i, acc):
        w = waves[i]
        return acc + jnp.sin(w[0] * z * 6.283 + w[1] * y * 6.283
                             + w[2] * x * 6.283 + phases[i])

    return jax.lax.fori_loop(0, _MODES, mode,
                             jnp.zeros(shape, jnp.float32)) / _MODES


@functools.partial(jax.jit, static_argnames=("names", "shape"))
def _fields(small, eye, storm, flow, names: tuple, shape):
    z, y, x = _axes(shape)
    vmax, rmax, dp, pitch = storm[0], storm[1], storm[2], storm[3]
    dy, dx = y - eye[0], x - eye[1]
    r = jnp.sqrt(dx * dx + dy * dy) + 1e-4
    theta = jnp.arctan2(dy, dx)
    # spiral rainbands: bands along log-spirals around the eye
    spiral = 3.0 * theta + pitch * jnp.log(r / rmax)
    bands = jnp.cos(spiral)
    ring = jnp.exp(-((r - 2.5 * rmax) / (4.0 * rmax)) ** 2)
    decay = 1.0 - 0.75 * z
    vt = vmax * 2.0 * (r / rmax) / (1.0 + (r / rmax) ** 2) * decay

    def field(name):
        if name == "U":
            return -vt * dy / r + flow[0] + 2.0 * small
        if name == "V":
            return vt * dx / r + flow[1] + 2.0 * small
        if name == "W":
            eyewall = jnp.exp(-((r - 1.2 * rmax) / (0.6 * rmax)) ** 2)
            return (6.0 * eyewall + 1.5 * ring * bands
                    - 0.8 * jnp.exp(-(r / rmax) ** 2)) \
                * jnp.sin(jnp.pi * z) + 0.4 * small
        if name == "P":
            return -dp * jnp.exp(-r / (3.0 * rmax)) * decay + 60.0 * small \
                + 300.0 * (y - 0.5)
        if name == "TC":
            return 30.0 - 95.0 * z + 9.0 * jnp.exp(-(r / (3.0 * rmax)) ** 2) \
                * jnp.sin(jnp.pi * z) + 0.8 * small
        if name == "QVAPOR":
            return jnp.maximum(0.022 * jnp.exp(-z / 0.22)
                               * (0.8 + 0.15 * ring * bands + 0.05 * small),
                               0.0)
        return jnp.maximum(_moisture(name, z, spiral, ring, small), 0.0)

    return tuple(jnp.broadcast_to(field(q), shape).astype(jnp.float32)
                 for q in names)


def _moisture(name, z, spiral, ring, small):
    if name == "CLOUD":
        return _moisture("QCLOUD", z, spiral, ring, small) \
            + _moisture("QICE", z, spiral, ring, small)
    if name == "PRECIP":
        return sum(_moisture(q, z, spiral, ring, small)
                   for q in ("QRAIN", "QSNOW", "QGRAUP"))
    peak, height, depth, offset, dry = _SPECIES[name]
    layer = jnp.exp(-((z - height) / depth) ** 2)
    s = ring * (0.75 * jnp.cos(spiral + offset) + 0.25 * small)
    return peak * layer * jnp.maximum(s - (dry - 0.5), 0.0) / (1.5 - dry)


def timestep(params: dict, t: int, shape, quantities=QUANTITIES) -> dict:
    """Timestep ``t`` of the cycle: ``{name: float32 (nz, ny, nx) array}``
    on the host, all made by one jitted call."""
    names = tuple(quantities)
    shape = tuple(int(s) for s in shape)
    small = _small(jnp.asarray(params["waves"]), jnp.asarray(params["phases"]),
                   shape=shape)
    out = _fields(small, jnp.asarray(params["eyes"][t]),
                  jnp.asarray(params["storm"]), jnp.asarray(params["flow"]),
                  names=names, shape=shape)
    return {q: np.asarray(f) for q, f in zip(names, out)}
