"""Region serving of an archive: closed-loop clients query a
``FieldRegionServer``.

Set-up steps the solver and appends ``archive.timesteps`` snapshots of the
configured quantities through ``CZDataset.append`` (the in-situ write
path), then decodes one chunk through a separate reader so that the window
compiles nothing.  The server starts with cold caches.  The archive is the
configuration's (its cloud placed by ``archive.placement_seed``) and the
same in every run: what a query costs depends on the data under its box,
so a run's seed draws only the queries, which then do the same work in
another order.

In the window, ``clients`` threads each take queries from a stream of their
own (:meth:`cellbench.traffic.ZipfBoxes.stream`, started from the seed) and
wait
for each answer before sending the next, until ``--seconds`` have passed.
``region_p95_ms`` is the 95th percentile of every completed query's
latency at the client; ``region_qps`` the queries completed over the
window.  A sample of answers drawn from the seed (each client keeps the
first answer of the largest box side and about ``sample_share`` of the
rest, at most ``sample_per_client``) is compared, once the window has
closed, with the same box of the field the solver produced.
"""
from __future__ import annotations

import os
import threading
import time

import numpy as np

from cellbench import check, solver
from cellbench.drivers.insitu import spec_of
from cellbench.harness import Outcome
from cellbench.traffic import ZipfBoxes


class Client:
    """One closed-loop client: its query stream, latencies and samples."""

    def __init__(self, gen: ZipfBoxes, mix: dict, seed: int, i: int):
        self.gen = gen
        self.queries = gen.stream(seed, i)
        self.pick = np.random.default_rng([seed, 4, i])
        self.share = float(mix["sample_share"])
        self.room = int(mix["sample_per_client"])
        self.largest = max(gen.sides)
        self.latencies: list[float] = []
        self.failed = 0
        self.kept: list = []
        self.kept_largest = False

    def keep(self, query, answer) -> None:
        side = query[3][0] - query[2][0]
        first_largest = side == self.largest and not self.kept_largest
        if len(self.kept) < self.room and (
                first_largest or self.pick.random() < self.share):
            self.kept.append((query, answer))
            self.kept_largest |= side == self.largest

    def loop(self, srv, ctx, start: threading.Barrier) -> None:
        start.wait()
        while not ctx.expired():
            query = next(self.queries)
            t, q, lo, hi = query
            with ctx.annotate("cb.query"):
                t0 = time.perf_counter()
                try:
                    answer = srv.query(q, t, lo, hi)
                except Exception:  # a failed request counts, the run goes on
                    self.failed += 1
                    answer = None
                self.latencies.append(time.perf_counter() - t0)
            self.keep(query, answer)


def run(ctx) -> Outcome:
    from repro import obs
    from repro.serve import FieldRegionServer
    from repro.store import CZDataset

    cfg, mix = ctx.config, ctx.traffic
    arch = cfg["archive"]
    qois = cfg["qois"]
    spec = spec_of(cfg, arch)
    # the archive's cloud is placed by archive.placement_seed, or by the
    # run's seed where the placement is "cells" (control.py)
    U = solver.initial_state(cfg, ctx.seed if cfg["placement"] == "cells"
                             else int(arch["placement_seed"]))
    dt = solver.cfl_dt(U)
    ctx.mark("initial_state")
    root = os.path.join(ctx.workdir, "archive")
    fields = {}
    with CZDataset(root, mode="a", spec=spec) as ds:
        for t in range(int(arch["timesteps"])):
            for _ in range(int(arch["steps_between"])):
                U = solver.step(U, dt)
            snap = {q: np.asarray(v) for q, v in solver.qois(U, qois).items()}
            ds.append(snap, time=float(t))
            fields.update({(t, q): v for q, v in snap.items()})
    del U
    ctx.mark("archive")
    with CZDataset(root, mode="r") as ds:
        ds.read_box(qois[0], 0, (0, 0, 0), (1, 1, 1))
    ctx.mark("warm_decode")

    gen = ZipfBoxes(mix, int(cfg["side"]), int(arch["timesteps"]), qois)
    clients = [Client(gen, mix, ctx.seed, i) for i in range(int(mix["clients"]))]
    decode_s = obs.REGISTRY.get("cz_reader_decode_seconds")
    server = cfg["server"]
    with FieldRegionServer(root, cache_bytes=int(server["cache_bytes"]),
                           cache_chunks=int(server["cache_chunks"]),
                           cache_readers=int(server["cache_readers"])) as srv:
        start = threading.Barrier(len(clients))
        threads = [threading.Thread(target=c.loop, args=(srv, ctx, start),
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
        for (t, q, lo, hi), answer in c.kept:
            ref = fields[(t, q)][tuple(slice(a, b) for a, b in zip(lo, hi))]
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
