"""Restart reads: closed-loop clients read whole fields of an archive
through ``FieldRegionServer``, as a solver restarting from a compressed
snapshot, or a visualization job loading whole fields, does.

Set-up writes the configuration's archive as ``serve.py`` does (the solver
stepped on the chip, each snapshot appended through ``CZDataset.append``),
then decodes one chunk through a separate reader so that the window
compiles nothing.  The server starts with cold caches.

Every query is one whole field, the box from the origin to the field's
shape.  Client i walks the archive's (timestep, quantity) members in
order, timesteps outer, starting at timestep ``start_stride * i``, and
waits for each answer before sending the next, until ``--seconds`` have
passed.  With the members further apart in that walk than the server pools
readers, no read finds its member's chunks decoded, as a restart's read
does not.  ``region_p95_ms`` is the 95th percentile of every completed
query's latency at the client; ``region_qps`` the queries completed over
the window.  Each client keeps its first answer and about
``sample_share`` of the others, at most ``sample_per_client`` (drawn from
the seed); once the window has closed they are compared with the fields
the solver produced.
"""
from __future__ import annotations

import os
import threading
import time

import numpy as np

from cellbench import check, solver
from cellbench.drivers.insitu import spec_of
from cellbench.harness import Outcome


class Client:
    """One closed-loop client: its walk over the members, latencies and
    kept answers."""

    def __init__(self, members: list, per_step: int, mix: dict, seed: int,
                 i: int):
        self.members = members
        self.next = int(mix["start_stride"]) * i * per_step % len(members)
        self.pick = np.random.default_rng([seed, 4, i])
        self.share = float(mix["sample_share"])
        self.room = int(mix["sample_per_client"])
        self.latencies: list[float] = []
        self.failed = 0
        self.kept: list = []

    def keep(self, member, answer) -> None:
        first = len(self.latencies) == 1
        if len(self.kept) < self.room and (
                first or self.pick.random() < self.share):
            self.kept.append((member, answer))

    def loop(self, srv, ctx, shape, start: threading.Barrier) -> None:
        start.wait()
        while not ctx.expired():
            member = self.members[self.next]
            self.next = (self.next + 1) % len(self.members)
            t, q = member
            with ctx.annotate("cb.query"):
                t0 = time.perf_counter()
                try:
                    answer = srv.query(q, t, (0, 0, 0), shape)
                except Exception:  # a failed request counts, the run goes on
                    self.failed += 1
                    answer = None
                self.latencies.append(time.perf_counter() - t0)
            self.keep(member, answer)


def write_archive(ctx, cfg: dict, root: str) -> dict:
    """The configuration's archive, appended as ``serve.py`` appends it;
    returns the fields by ``(t, quantity)``."""
    from repro.store import CZDataset

    arch = cfg["archive"]
    qois = cfg["qois"]
    U = solver.initial_state(cfg, ctx.seed if cfg["placement"] == "cells"
                             else int(arch["placement_seed"]))
    dt = solver.cfl_dt(U)
    ctx.mark("initial_state")
    fields = {}
    with CZDataset(root, mode="a", spec=spec_of(cfg, arch)) as ds:
        for t in range(int(arch["timesteps"])):
            for _ in range(int(arch["steps_between"])):
                U = solver.step(U, dt)
            snap = {q: np.asarray(v) for q, v in solver.qois(U, qois).items()}
            ds.append(snap, time=float(t))
            fields.update({(t, q): v for q, v in snap.items()})
    return fields


def run(ctx) -> Outcome:
    from repro import obs
    from repro.serve import FieldRegionServer
    from repro.store import CZDataset

    cfg, mix = ctx.config, ctx.traffic
    arch = cfg["archive"]
    qois = cfg["qois"]
    root = os.path.join(ctx.workdir, "archive")
    fields = write_archive(ctx, cfg, root)
    ctx.mark("archive")
    with CZDataset(root, mode="r") as ds:
        ds.read_box(qois[0], 0, (0, 0, 0), (1, 1, 1))
    ctx.mark("warm_decode")

    shape = (int(cfg["side"]),) * 3
    members = [(t, q) for t in range(int(arch["timesteps"])) for q in qois]
    clients = [Client(members, len(qois), mix, ctx.seed, i)
               for i in range(int(mix["clients"]))]
    decode_s = obs.REGISTRY.get("cz_reader_decode_seconds")
    server = cfg["server"]
    with FieldRegionServer(root, cache_bytes=int(server["cache_bytes"]),
                           cache_chunks=int(server["cache_chunks"]),
                           cache_readers=int(server["cache_readers"])) as srv:
        start = threading.Barrier(len(clients))
        threads = [threading.Thread(target=c.loop,
                                    args=(srv, ctx, shape, start),
                                    name=f"client-{i}")
                   for i, c in enumerate(clients)]
        hist0 = decode_s.snapshot()
        with ctx.window():
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        hist1 = decode_s.snapshot()
        stats = srv.stats()
        cache = srv.cache.stats()

    latencies = np.concatenate([np.asarray(c.latencies) for c in clients])
    failed = sum(c.failed for c in clients)
    readings = check.Readings(bound=arch["bound_factor"] * cfg["eps"],
                              eps=cfg["eps"], mismatches=failed)
    for c in clients:
        for member, answer in c.kept:
            ref = fields[member]
            readings.add(check.bfloat16(ref) if ctx.control else answer, ref)

    done = len(latencies) - failed
    metrics = {"region_p95_ms": float(np.percentile(latencies, 95)) * 1e3,
               "region_qps": done / ctx.window_s}
    counters = {
        "region_cache_hits": cache["hits"],
        "region_cache_misses": cache["misses"],
        "chunk_decode_s": hist1["sum"] - hist0["sum"],
        "chunk_decodes": hist1["count"] - hist0["count"],
        "kernel_elements": {"wavelet_inverse": stats["bytes_decoded"] // 4},
    }
    return Outcome(metrics, attempted=len(latencies), failed=failed,
                   readings=readings, counters=counters)
