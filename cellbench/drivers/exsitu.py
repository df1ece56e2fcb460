"""Ex-situ archiving: snapshots written by a simulation as one file per
field and timestep are read from those files and committed to a
``CZDataset`` through ``append``, one timestep at a time, by one writer.

The spec comes first, so that a program that cannot take it (a ``bound``
it does not know) fails at once.  Set-up then makes the configuration's
cycle of ``timesteps`` (K) generated timesteps (``cellbench/isabel.py``,
the configuration's storm, the same in every run; the seed draws the
timesteps checked) and writes each field to a ``.npy`` file, the input
``cz-compress`` takes.  It appends the first timestep once into a
throw-away dataset, so that the window compiles nothing: every member has
the same shape, and its own ``eps`` reaches the kernels as an operand.

The window loops over the cycle: ``np.load`` of the next timestep's files
(``cb.read``; written in set-up, they sit in the page cache), then
``append`` of them (``cb.append``).  It closes once ``--seconds`` have
passed and at least one whole cycle is committed, once the timestep in
flight is committed.

``compress_GBps`` is the raw bytes of every committed member (the fields'
own points, not the padding of their edge blocks) over the window.
``compress_ratio`` is the raw bytes over the member bytes of the first
cycle, the same fields in every run whatever the program's speed.  Once
the window has closed, the manifest is read back from the store, and the
members of a sample of timesteps drawn from the seed and of the window's
last timestep are decoded through the store and compared with the files
they were read from.  Each member is held to the configuration's bound,
worked out here from the file: ``eps`` times the field's own value range
(a constant field: exactly).  The ``eps`` a member's header records must
be that bound; one that differs counts as a mismatch.
"""
from __future__ import annotations

import os
import shutil

import numpy as np

from cellbench import check, isabel
from cellbench.drivers.insitu import Reservoir, manifest_mismatches
from cellbench.harness import Outcome

#: the program's counters of stage-1 elements and of edge-block padding
COUNTERS = {"stage1_elements": "cz_stage1_elements_total",
            "pad_elements": "cz_blockify_pad_elements_total"}


def spec_of(cfg: dict, mix: dict):
    from repro.core import CompressionSpec

    return CompressionSpec(scheme=mix["scheme"], eps=cfg["eps"],
                           bound=cfg["bound"], block_size=cfg["block"],
                           shuffle=cfg["shuffle"], stage2=cfg["stage2"],
                           buffer_bytes=cfg["buffer_bytes"],
                           device=cfg["device"])


def write_cycle(cfg: dict, root: str) -> list[dict]:
    """The cycle's fields as ``.npy`` files: ``[{quantity: path}, ...]``,
    one dict per timestep.  The storm is the configuration's
    (``storm_seed``), the same in every run, as the in-situ cells' fields
    are: what a member costs and how far it compresses depend on its
    data."""
    params = isabel.track(int(cfg["storm_seed"]), int(cfg["timesteps"]))
    files = []
    for t in range(int(cfg["timesteps"])):
        fields = isabel.timestep(params, t, cfg["shape"], cfg["quantities"])
        paths = {}
        for q, f in fields.items():
            paths[q] = os.path.join(root, f"{q}.t{t:02d}.npy")
            np.save(paths[q], f)
        files.append(paths)
    return files


def load(paths: dict) -> dict:
    return {q: np.load(p) for q, p in paths.items()}


def counters() -> dict:
    """The program's counters that exist, by their names here."""
    from repro import obs

    return {k: obs.REGISTRY.get(name).value()
            for k, name in COUNTERS.items() if name in obs.REGISTRY}


def padded_elements(shape, block: int) -> int:
    """Elements of one field's blocks, its edge blocks padded."""
    return int(np.prod([-(-int(n) // block) for n in shape])) * block ** 3


def worsen(worst: check.Readings, member: check.Readings) -> None:
    """Fold one member's readings, each against its own eps, into the
    worst so far."""
    worst.max_err_tol = max(worst.max_err_tol, member.max_err_tol)
    worst.rms_err_eps = max(worst.rms_err_eps, member.rms_err_eps)
    worst.mismatches += member.mismatches
    worst.compared += member.compared


def member_readings(cfg: dict, mix: dict, ref: np.ndarray, recorded,
                    answer) -> check.Readings:
    """One member against the configuration's bound for the field it was
    read from, ``eps × (max − min)``: the bound the deployment promises,
    not the one the program recorded.  A constant field must come back
    exactly.  ``recorded`` is the ``eps`` of the member's header (None if
    it could not be read), which must be that bound to within rounding."""
    eps = cfg["eps"] * (float(ref.max()) - float(ref.min()))
    if eps == 0:
        out = check.Readings(bound=0.0, eps=1.0, compared=1)
        out.mismatches = int(answer is None
                             or not np.array_equal(answer, ref))
    else:
        out = check.Readings(bound=mix["bound_factor"] * eps, eps=eps)
        out.add(answer, ref)
    if recorded is None or abs(recorded - eps) > 1e-6 * eps:
        out.mismatches += 1
    return out


def run(ctx) -> Outcome:
    from repro.store import CZDataset

    cfg, mix = ctx.config, ctx.traffic
    spec = spec_of(cfg, mix)
    qois = cfg["quantities"]
    cycle = int(cfg["timesteps"])
    inputs = os.path.join(ctx.workdir, "input")
    os.makedirs(inputs)
    files = write_cycle(cfg, inputs)
    ctx.mark("generate")

    warm = os.path.join(ctx.workdir, "warm")
    with CZDataset(warm, mode="a", spec=spec) as ds:
        ds.append(load(files[0]))
    shutil.rmtree(warm)
    ctx.mark("warm_append")

    root = os.path.join(ctx.workdir, "run")
    sample = Reservoir(int(mix["sampled_timesteps"]),
                       np.random.default_rng([ctx.seed, 2]))
    steps = 0
    with CZDataset(root, mode="a", spec=spec) as ds:
        before = counters()
        with ctx.window():
            while True:
                with ctx.annotate("cb.read"):
                    fields = load(files[steps % cycle])
                with ctx.annotate("cb.append"):
                    ds.append(fields, time=float(steps))
                sample.offer(steps, steps)
                steps += 1
                if steps >= cycle and ctx.expired():
                    break
        after = counters()
    del fields
    bad, records = manifest_mismatches(root, qois, steps)
    raw = sum(r["raw_bytes"] for r in records)
    first = [r for r in records if r["t"] < cycle]
    cycle_raw = sum(r["raw_bytes"] for r in first)
    cycle_bytes = sum(r["bytes"] for r in first)

    worst = check.Readings(bound=0.0, eps=1.0, mismatches=bad)
    with CZDataset(root, mode="r") as ds:
        for t in sorted({*sample.kept, steps - 1}):
            for q, ref in load(files[t % cycle]).items():
                try:
                    recorded = ds.reader(q, t).spec.eps
                    answer = (check.bfloat16(ref) if ctx.control
                              else ds.read_field(q, t))
                except (KeyError, ValueError, OSError):
                    recorded = answer = None
                worsen(worst, member_readings(cfg, mix, ref, recorded,
                                              answer))
    members = steps * len(qois)
    metrics = {"compress_GBps": raw / ctx.window_s / 1e9,
               "compress_ratio": (cycle_raw / cycle_bytes if cycle_bytes
                                  else 0.0)}
    elements = members * padded_elements(cfg["shape"], int(cfg["block"]))
    out = {"members": members, "raw_bytes": raw,
           "kernel_elements": {mix["kernel"]: elements}}
    out.update({k: after[k] - before[k] for k in after})
    return Outcome(metrics, attempted=members, failed=0, readings=worst,
                   counters=out)
