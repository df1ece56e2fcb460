"""Traffic generators, driven by the parameters of ``traffic/<mix>.json``.

:class:`ZipfBoxes` draws region queries ``(timestep, quantity, lo, hi)`` over
an archive, as analysis and visualization clients send them: a box side
from fixed shares, then one key of that side from a Zipf law over
``(timestep, quantity, origin)`` (YCSB workload C's skew; the weights are
those of ``benchmarks/bench_serve.py``).  Origins are unaligned, and they
and the popularity order are drawn from the mix's ``keys_seed``: the hot
set is part of the mix and the same for every run.

A client's queries come in rounds of ``round`` queries that hold the box
sides in exactly their shares, in an order drawn from the run's seed; each
query's key is drawn from its side's Zipf law.  Independent draws of the
side would let the share of the costly large boxes, and with it the rate,
swing by several percent from seed to seed: in rounds, seeds give the same
sizes in another order.
"""
from __future__ import annotations

import numpy as np


def zipf_weights(k: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, k + 1) ** a
    return w / w.sum()


class ZipfBoxes:
    """Region keys for one archive, and each client's stream of queries."""

    def __init__(self, mix: dict, side: int, timesteps: int, qois):
        rng = np.random.default_rng(int(mix["keys_seed"]))
        self.sides = [int(s) for s in mix["box_sides"]]
        per_round = np.asarray(mix["box_shares"]) * int(mix["round"])
        self.round = np.repeat(np.arange(len(self.sides)),
                               np.rint(per_round).astype(int))
        if not np.allclose(per_round, np.rint(per_round)):
            raise ValueError("box_shares times round must be whole numbers")
        k = int(mix["origins_per_side"])
        self.keys = []      # per side: [(t, q, lo), ...] in popularity order
        for s in self.sides:
            origins = rng.integers(0, side - s + 1, (k, 3))
            keys = [(t, q, tuple(int(v) for v in lo))
                    for t in range(timesteps) for q in qois for lo in origins]
            order = rng.permutation(len(keys))
            self.keys.append([keys[i] for i in order])
        self.key_cdf = [np.cumsum(zipf_weights(len(ks), mix["zipf_s"]))
                        for ks in self.keys]

    def query(self, side: int, u_key: float):
        """The query ``(t, quantity, lo, hi)`` of box side number ``side``
        at quantile ``u_key`` of that side's Zipf law."""
        cdf = self.key_cdf[side]
        ki = min(int(np.searchsorted(cdf, u_key * cdf[-1], side="right")),
                 len(cdf) - 1)
        t, q, lo = self.keys[side][ki]
        s = self.sides[side]
        return t, q, lo, tuple(v + s for v in lo)

    def stream(self, seed: int, client: int):
        """Client ``client``'s endless queries in run ``seed``."""
        rng = np.random.default_rng([seed, 3, client])
        while True:
            for side in rng.permutation(self.round):
                yield self.query(int(side), rng.random())
