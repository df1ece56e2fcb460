"""The in-situ driver at 64^3 on the CPU: a rehearsal of each mix, its
traced run, the control, and the faults a compression cell can have, each
of which must come out as not correct."""
from __future__ import annotations

import itertools

import numpy as np
import pytest

from cellbench_testlib import run_small

from cellbench import check, harness, registry

INSITU = ["insitu_wavelet", "insitu_zfpx", "insitu_lorenzo"]


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


@pytest.mark.parametrize("workload", INSITU)
def test_ratio_does_not_follow_the_window(workload, tmp_path):
    """``compress_ratio`` is that of the first cycle of dumps: a window that
    closes at once and one of several seconds read the same ratio."""
    short = run_small(workload, seconds=0.0, workdir=tmp_path / "short")
    long = run_small(workload, seconds=3.0, workdir=tmp_path / "long")
    assert short["correct"] and long["correct"], (short, long)
    assert long["attempted"] > short["attempted"]
    assert (short["metrics"]["compress_ratio"]["value"]
            == long["metrics"]["compress_ratio"]["value"])


def _polls_until(n: int):
    """``Context.expired`` that answers yes at its ``n``-th poll."""
    polls = itertools.count(1)
    return lambda self: next(polls) >= n


def test_cycle_repeats_its_dumps(monkeypatch, tmp_path):
    """Dump K of the window starts again from the initial state: its members
    hold the same chunk bytes as dump 0's, and dump K+1's those of dump 1."""
    from repro.core import container

    monkeypatch.setattr(harness.Context, "expired", _polls_until(3))
    r = run_small("insitu_zfpx", mix={"cycle_dumps": 2}, workdir=tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 4 * 3

    def chunks(q, t):
        path = tmp_path / "run" / q / f"t{t:06d}.cz"
        return list(container.iter_compressed(str(path)))

    for q in registry.config("cavitation_insitu_256")["qois"]:
        assert chunks(q, 2) == chunks(q, 0)
        assert chunks(q, 3) == chunks(q, 1)
        assert chunks(q, 1) != chunks(q, 0)


def test_window_commits_a_whole_cycle(tmp_path):
    """A deadline that passes before K dumps still commits K, and no more."""
    r = run_small("insitu_lorenzo", seconds=0.0, mix={"cycle_dumps": 3},
                  workdir=tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 3 * 3
    assert r["checks"]["mismatches"]["value"] == 0


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


def test_stale_dumps_fail_when_the_last_repeats_dump_0(monkeypatch, tmp_path):
    """Three dumps of a cycle of two: the last is dump 0 again, so a store
    that commits dump 0's fields every time is caught by the first cycle's
    last dump, which is always compared."""
    _stale_append(monkeypatch)
    monkeypatch.setattr(harness.Context, "expired", _polls_until(2))
    r = run_small("insitu_wavelet", mix={"cycle_dumps": 2, "sampled_dumps": 0},
                  workdir=tmp_path)
    assert r["attempted"] == 3 * 3
    assert not r["correct"], r["checks"]
