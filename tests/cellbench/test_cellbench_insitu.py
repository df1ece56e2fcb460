"""The in-situ driver at 64^3 on the CPU: a rehearsal of each mix, its
traced run, the control, and the faults a compression cell can have, each
of which must come out as not correct."""
from __future__ import annotations

import numpy as np
import pytest

from cellbench_testlib import run_small

from cellbench import check, registry


def _assert_well_formed(result, workload, trace):
    bench = registry.benchmark()
    cell = registry.cell(bench, workload)
    want = (registry.per_layer if trace else registry.end_to_end)(bench, cell)
    names = {m["name"] for m in want}
    assert set(result["metrics"]) <= names
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(check.NAMES)
    assert result["window"]["compiles"] == 0
    assert result["device"]["platform"] == "cpu"
    return names


@pytest.mark.parametrize("workload", ["insitu_wavelet", "insitu_zfpx"])
def test_rehearsal(workload, tmp_path):
    r = run_small(workload, seconds=2.0, workdir=tmp_path)
    names = _assert_well_formed(r, workload, trace=False)
    assert set(r["metrics"]) == names
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 3 * 2 and r["failed"] == 0
    assert 1 < r["metrics"]["compress_ratio"]["value"] < 1000


def test_traced_rehearsal(tmp_path):
    r = run_small("insitu_wavelet", trace=True, workdir=tmp_path)
    _assert_well_formed(r, "insitu_wavelet", trace=True)
    assert r["correct"], r["checks"]
    # the CPU has no device plane: only the program's spans can be read
    assert {"stage1_ms.compress", "stage2_ms.compress"} <= set(r["metrics"])
    assert "busy_s" not in r["device"]


@pytest.mark.parametrize("workload", ["insitu_wavelet", "insitu_zfpx"])
def test_control_is_not_correct(workload, tmp_path):
    """The reference in the program's place, rounded to bfloat16."""
    r = run_small(workload, control=True, workdir=tmp_path)
    assert not r["correct"]
    rms = r["checks"]["rms_err/eps"]
    assert rms["value"] > rms["limit"]


def test_cells_placement_is_correct_and_control_is_not(tmp_path):
    """The cloud at an off-block offset (``control.py --placement cells``):
    the program stays within its limits, the control does not."""
    cells = {"placement": "cells"}
    r = run_small("insitu_wavelet", config=cells, workdir=tmp_path / "a")
    assert r["correct"], r["checks"]
    c = run_small("insitu_wavelet", config=cells, control=True,
                  workdir=tmp_path / "b")
    assert not c["correct"]


def _stale_append(monkeypatch):
    """A dump that commits the state it committed first."""
    from repro.store import CZDataset

    orig, first = CZDataset.append, {}

    def append(self, fields, time=None):
        if not first:
            first.update({q: np.asarray(v) for q, v in fields.items()})
        return orig(self, first, time=time)

    monkeypatch.setattr(CZDataset, "append", append)


def _half_batch(monkeypatch):
    """Stage 1 over the first half of the blocks, zeros for the rest."""
    from repro.core.schemes.wavelet import WaveletScheme

    orig = WaveletScheme.stage1

    def stage1(self, blocks_np, spec):
        blocks_np = np.array(blocks_np)
        blocks_np[len(blocks_np) // 2:] = 0
        return orig(self, blocks_np, spec)

    monkeypatch.setattr(WaveletScheme, "stage1", stage1)


def _altered_answer(monkeypatch):
    """One coarse coefficient altered where stage 1 produces it."""
    from repro.core.schemes.wavelet import WaveletScheme

    orig = WaveletScheme.stage1

    def stage1(self, blocks_np, spec):
        s1 = orig(self, blocks_np, spec)
        s1["coarse"] = s1["coarse"].copy()
        s1["coarse"][0, 0, 0, 0] += 1.0
        return s1

    monkeypatch.setattr(WaveletScheme, "stage1", stage1)


@pytest.mark.parametrize("fault", [_stale_append, _half_batch, _altered_answer],
                         ids=["state_unchanged", "half_batch", "altered"])
def test_fault_is_not_correct(fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    # a window of a few seconds makes several dumps even on a loaded host
    r = run_small("insitu_wavelet", seconds=3.0, workdir=tmp_path)
    assert r["attempted"] >= 3 * 2
    assert not r["correct"], r["checks"]
