"""The restart driver over a 64^3 archive on the CPU: a rehearsal, its
traced run, the control, and a fault, each fault coming out as not
correct.  The server's caches are cut with the fields, so that, as at the
cell's own size, no member is found decoded when it is read again."""
from __future__ import annotations

import numpy as np

from cellbench_testlib import run_small

from cellbench import check

W = "serve_full_field"
#: 64^3 members are 1 MiB: a 1 MiB region cache and 4 readers keep the
#: walk's reuse distance of 24 members above what the caches hold
SMALL_SERVER = {"server": {"cache_bytes": 1 << 20, "cache_readers": 4,
                           "cache_chunks": 32}}


def _run(tmp_path, **kw):
    return run_small(W, workdir=tmp_path, config=SMALL_SERVER, **kw)


def test_rehearsal(tmp_path):
    r = _run(tmp_path)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"region_p95_ms", "region_qps", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] > 24 and r["failed"] == 0
    assert r["window"]["compiles"] == 0
    assert list(r)[-1] == "checks" and set(r["checks"]) == set(check.NAMES)


def test_traced_rehearsal_reads_cold(tmp_path):
    r = _run(tmp_path, trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["region_cache_hit_rate"]["value"] < 10
    assert r["metrics"]["chunk_decode_ms"]["value"] > 0


def test_clients_walk_the_members_from_their_own_timestep():
    from cellbench.drivers.restart import Client

    members = [(t, q) for t in range(8) for q in ("p", "rho", "E")]
    mix = {"start_stride": 4, "sample_share": 0.05, "sample_per_client": 2}
    assert [members[Client(members, 3, mix, 1, i).next] for i in (0, 1)] == \
        [(0, "p"), (4, "p")]


def test_control_is_not_correct(tmp_path):
    r = _run(tmp_path, control=True)
    assert not r["correct"]


def test_fault_is_not_correct(monkeypatch, tmp_path):
    """One value of every answer altered where it is produced."""
    from repro.serve import FieldRegionServer

    orig = FieldRegionServer.query

    def query(self, *a, **k):
        out = np.array(orig(self, *a, **k))
        out[0, 0, 0] += 1.0
        return out

    monkeypatch.setattr(FieldRegionServer, "query", query)
    r = _run(tmp_path)
    assert not r["correct"], r["checks"]
