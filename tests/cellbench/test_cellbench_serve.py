"""The serving driver over a 64^3 archive on the CPU: a rehearsal, its
traced run, the control, and the faults a serving cell can have, each of
which must come out as not correct."""
from __future__ import annotations

import numpy as np
import pytest

from cellbench_testlib import run_small

from cellbench import check

W = "serve_zipf_boxes"


def test_rehearsal(tmp_path):
    r = run_small(W, workdir=tmp_path)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"region_p95_ms", "region_qps", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] > 100 and r["failed"] == 0
    assert r["window"]["compiles"] == 0
    assert list(r)[-1] == "checks" and set(r["checks"]) == set(check.NAMES)


def test_traced_rehearsal(tmp_path):
    r = run_small(W, trace=True, workdir=tmp_path)
    assert r["correct"], r["checks"]
    assert {"region_cache_hit_rate", "chunk_decode_ms"} <= set(r["metrics"])
    assert 0 < r["metrics"]["region_cache_hit_rate"]["value"] < 100


def test_control_is_not_correct(tmp_path):
    r = run_small(W, control=True, workdir=tmp_path)
    assert not r["correct"]
    assert r["checks"]["rms_err/eps"]["value"] > r["checks"]["rms_err/eps"]["limit"]


def _wrap_query(monkeypatch, alter):
    from repro.serve import FieldRegionServer

    orig = FieldRegionServer.query

    def query(self, *a, **k):
        return alter(orig(self, *a, **k))

    monkeypatch.setattr(FieldRegionServer, "query", query)


def _stale(monkeypatch):
    """Every query answered with the first answer served."""
    first = []

    def alter(out):
        if not first:
            first.append(out)
        return first[0]

    _wrap_query(monkeypatch, alter)


def _half_box(monkeypatch):
    """Half of the box left out."""
    def alter(out):
        out = np.array(out)
        out[out.shape[0] // 2:] = 0
        return out

    _wrap_query(monkeypatch, alter)


def _altered(monkeypatch):
    """One value of every answer altered where it is produced."""
    def alter(out):
        out = np.array(out)
        out[0, 0, 0] += 1.0
        return out

    _wrap_query(monkeypatch, alter)


@pytest.mark.parametrize("fault", [_stale, _half_box, _altered],
                         ids=["state_unchanged", "half_batch", "altered"])
def test_fault_is_not_correct(fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    r = run_small(W, workdir=tmp_path)
    assert not r["correct"], r["checks"]
