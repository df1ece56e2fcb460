"""In-situ compression: the solver steps a field on the chip, and each dump
of its quantities is committed to a ``CZDataset`` through ``append``.

Set-up builds the initial state on the device from the seed and makes one
dump into a throw-away dataset, which compiles every program the window
runs.  The window then alternates ``steps_per_dump`` solver steps with one
dump.  After every ``cycle_dumps`` (K) dumps it starts again from the
initial state, which set-up keeps on the device: dump i of every run is
dump i mod K of one fixed sequence of fields, however fast the program
runs.  The window closes when ``--seconds`` have passed and at least K
dumps are committed, once the dump in flight is committed.

``compress_GBps`` is the raw bytes of every committed quantity over the
whole window, solver steps included.  ``compress_ratio`` is the raw bytes
over the committed member bytes of the first K dumps, one whole cycle: the
same fields in every run, so a faster program, which fits more dumps into
the window, reads the same ratio.  Once the window has closed, the
manifest is read back from the store (every dump committed, every member
of its recorded size), and the members of a sample of dumps drawn from the
seed (reservoir sampling), of the first cycle's last dump and of the
window's last dump are decoded through the store and compared with the
fields the solver produced.
"""
from __future__ import annotations

import json
import os
import shutil

import numpy as np

from cellbench import check, solver
from cellbench.harness import Outcome


def spec_of(cfg: dict, mix: dict):
    from repro.core import CompressionSpec

    return CompressionSpec(scheme=mix["scheme"], eps=cfg["eps"],
                           block_size=cfg["block"], shuffle=cfg["shuffle"],
                           stage2=cfg["stage2"],
                           buffer_bytes=cfg["buffer_bytes"],
                           device=cfg["device"])


class Reservoir:
    """A uniform sample of ``k`` dumps, drawn from the seed, of a stream
    whose length is not known in advance."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.kept = k, rng, {}

    def offer(self, i: int, item) -> None:
        if i < self.k:
            self.kept[i] = item
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.k:
            slot = sorted(self.kept)[j]
            del self.kept[slot]
            self.kept[i] = item


def manifest_mismatches(root: str, qois, dumps: int) -> tuple[int, list]:
    """Uncommitted or mis-sized members, read back from the store's files;
    and the committed timestep records."""
    with open(os.path.join(root, "manifest.json")) as f:
        manifest = json.load(f)
    bad, records = 0, []
    for q in qois:
        ent = manifest["quantities"].get(q)
        steps = ent["timesteps"] if ent else []
        bad += abs(len(steps) - dumps)
        for i, rec in enumerate(steps):
            path = os.path.join(root, rec["file"])
            if rec["t"] != i or not os.path.isfile(path) \
                    or os.path.getsize(path) != rec["bytes"]:
                bad += 1
            records.append(rec)
    return bad, records


def run(ctx) -> Outcome:
    from repro.store import CZDataset

    cfg, mix = ctx.config, ctx.traffic
    qois = cfg["qois"]
    spec = spec_of(cfg, mix)
    U0 = solver.initial_state(cfg, ctx.seed)
    dt = solver.cfl_dt(U0)
    steps = int(mix["steps_per_dump"])
    cycle = int(mix["cycle_dumps"])
    ctx.mark("initial_state")

    def advance(U):
        for _ in range(steps):
            U = solver.step(U, dt)
        return U

    warm = os.path.join(ctx.workdir, "warm")
    with CZDataset(warm, mode="a", spec=spec) as ds:
        ds.append(solver.qois(advance(U0), qois), time=0.0)
    shutil.rmtree(warm)
    ctx.mark("warm_dump")

    root = os.path.join(ctx.workdir, "run")
    sample = Reservoir(int(mix["sampled_dumps"]),
                       np.random.default_rng([ctx.seed, 2]))
    checked = {}
    dumps = 0
    with CZDataset(root, mode="a", spec=spec) as ds, ctx.window():
        while True:
            with ctx.annotate("cb.solver_step"):
                U = advance(U0 if dumps % cycle == 0 else U)
                fields = solver.qois(U, qois)
            with ctx.annotate("cb.append"):
                ds.append(fields, time=float(dumps + 1) * steps)
            sample.offer(dumps, fields)
            if dumps == cycle - 1:
                checked[dumps] = fields
            last = (dumps, fields)
            dumps += 1
            if dumps >= cycle and ctx.expired():
                break
    bad, records = manifest_mismatches(root, qois, dumps)
    raw = sum(r["raw_bytes"] for r in records)
    first = [r for r in records if r["t"] < cycle]
    cycle_raw = sum(r["raw_bytes"] for r in first)
    cycle_bytes = sum(r["bytes"] for r in first)
    checked.update({**sample.kept, last[0]: last[1]})
    del U0, U, fields, sample, last

    readings = check.Readings(bound=mix["bound_factor"] * cfg["eps"],
                              eps=cfg["eps"], mismatches=bad)
    with CZDataset(root, mode="r") as ds:
        for t in sorted(checked):
            for q in qois:
                ref = np.asarray(checked[t][q])
                if ctx.control:
                    answer = check.bfloat16(ref)
                else:
                    try:
                        answer = ds.read_field(q, t)
                    except (KeyError, ValueError, OSError):
                        answer = None
                readings.add(answer, ref)
    members = dumps * len(qois)
    metrics = {"compress_GBps": raw / ctx.window_s / 1e9,
               "compress_ratio": (cycle_raw / cycle_bytes if cycle_bytes
                                  else 0.0)}
    counters = {"members": members, "raw_bytes": raw,
                "kernel_elements": {mix["kernel"]: raw // 4}}
    return Outcome(metrics, attempted=members, failed=0, readings=readings,
                   counters=counters)
