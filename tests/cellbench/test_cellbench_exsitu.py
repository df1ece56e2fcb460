"""The ex-situ driver on the CPU, at a small shape that is not a multiple of
its block: the ISABEL generator, a rehearsal, its traced run with the
padding's span and counters, the control, a fault, and the readers of the
padding metrics on a program that has neither."""
from __future__ import annotations

import numpy as np
import pytest

from cellbench_testlib import run_small

from cellbench import check, harness, isabel, registry

W = "exsitu_isabel_lorenzo"
#: 10x20x20 at block 8: 2x3x3 blocks, 56.6% of stage 1's elements padding
SMALL_ISABEL = {"shape": [10, 20, 20], "block": 8, "buffer_bytes": 8192,
                "quantities": ["P", "QRAIN", "U"]}


def _run(tmp_path, **kw):
    return run_small(W, workdir=tmp_path, config=SMALL_ISABEL, **kw)


def test_generator_names_shape_dtype_and_seed():
    cfg = registry.config("hurricane_isabel_exsitu")
    assert tuple(cfg["quantities"]) == isabel.QUANTITIES
    assert len(isabel.QUANTITIES) == 13 and cfg["shape"] == [100, 500, 500]
    shape = (8, 24, 24)
    a = isabel.timestep(isabel.track(7, 2), 1, shape)
    b = isabel.timestep(isabel.track(7, 2), 1, shape)
    c = isabel.timestep(isabel.track(8, 2), 1, shape)
    assert list(a) == list(isabel.QUANTITIES)
    for q in isabel.QUANTITIES:
        assert a[q].shape == shape and a[q].dtype == np.float32
        np.testing.assert_array_equal(a[q], b[q])
        assert np.isfinite(a[q]).all() and a[q].max() > a[q].min()
        assert not np.array_equal(a[q], c[q])
    for q in ("CLOUD", "PRECIP", "QCLOUD", "QGRAUP", "QICE", "QRAIN",
              "QSNOW", "QVAPOR"):
        assert a[q].min() >= 0
    for q in ("QCLOUD", "QGRAUP", "QICE", "QRAIN", "QSNOW"):
        assert (a[q] == 0).mean() > 0.5, q
    # the eye moves with the timestep
    t0 = isabel.timestep(isabel.track(7, 2), 0, shape, ["P"])["P"]
    assert not np.array_equal(t0, a["P"])


def test_rehearsal(tmp_path):
    r = _run(tmp_path, seconds=1.0)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"compress_GBps", "compress_ratio", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["window"]["compiles"] == 0
    assert r["attempted"] % 3 == 0 and r["attempted"] >= 2 * 3
    assert list(r)[-1] == "checks" and set(r["checks"]) == set(check.NAMES)


def test_traced_rehearsal_reads_the_padding(tmp_path):
    r = _run(tmp_path, seconds=0.5, trace=True)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["pad_share.compress"]["value"] == pytest.approx(
        100 * (18 * 512 - 4000) / (18 * 512))
    assert m["pad_ms.compress"]["value"] > 0
    assert {"stage1_ms.compress", "stage2_wall_ms.compress",
            "store_ms.compress"} <= set(m)


def test_raw_bytes_are_the_fields_own(tmp_path):
    """The members' raw bytes are the fields' points, not their padded
    blocks, in the manifest and so in ``compress_GBps``."""
    import json

    r = _run(tmp_path, seconds=0.0)
    with open(tmp_path / "run" / "manifest.json") as f:
        manifest = json.load(f)
    for ent in manifest["quantities"].values():
        assert {ts["raw_bytes"] for ts in ent["timesteps"]} == {4 * 10 * 20 * 20}
    assert manifest["spec"]["bound"] == "range"
    assert r["correct"]


def test_ratio_does_not_follow_the_seed(tmp_path):
    """The storm is the configuration's: two seeds archive the same fields
    and read the same ``compress_ratio``."""
    a = _run(tmp_path / "a", seconds=0.0)
    b = _run(tmp_path / "b", seconds=0.0, seed=2 ** 31 + 777)
    assert a["correct"] and b["correct"]
    assert (a["metrics"]["compress_ratio"]["value"]
            == b["metrics"]["compress_ratio"]["value"])


def test_control_is_not_correct(tmp_path):
    r = _run(tmp_path, seconds=0.0, control=True)
    assert not r["correct"]
    assert r["checks"]["max_err/tol"]["value"] > 1


def test_fault_is_not_correct(monkeypatch, tmp_path):
    """A member decoded at twice its recorded eps is caught against the
    bound of the field it was read from."""
    from repro.store import CZDataset

    orig = CZDataset.read_field

    def coarse(self, q, t):
        out = orig(self, q, t)
        eps = self.reader(q, t).spec.eps
        return (np.round(out / (4 * eps)) * 4 * eps).astype(out.dtype)

    monkeypatch.setattr(CZDataset, "read_field", coarse)
    r = _run(tmp_path, seconds=0.0)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("factor", [10.0, 1.5])
def test_a_loosened_eps_is_not_correct(monkeypatch, tmp_path, factor):
    """A program that resolves each member's eps wider than the
    configuration's bound, and encodes and records it so, is caught: the
    check works the bound out from the field read, not from the header."""
    import dataclasses

    from repro.core import CompressionSpec

    orig = CompressionSpec.resolve

    def loose(self, field):
        spec = orig(self, field)
        return dataclasses.replace(spec, eps=spec.eps * factor)

    monkeypatch.setattr(CompressionSpec, "resolve", loose)
    r = _run(tmp_path, seconds=0.0)
    assert not r["correct"], r["checks"]
    assert r["checks"]["mismatches"]["value"] > 0


def test_member_readings_hold_the_configured_bound():
    from cellbench.drivers import exsitu

    cfg, mix = {"eps": 1e-4}, {"bound_factor": 1.0}
    ref = np.linspace(-3.0, 5.0, 1000, dtype=np.float32).reshape(10, 10, 10)
    eps = 1e-4 * 8.0
    good = exsitu.member_readings(cfg, mix, ref, eps, ref + 0.9 * eps)
    assert good.mismatches == 0 and good.max_err_tol < 1
    # a recorded eps off the bound is a mismatch, even with exact values
    assert exsitu.member_readings(cfg, mix, ref, 2 * eps, ref).mismatches == 1
    assert exsitu.member_readings(cfg, mix, ref, None, ref).mismatches == 1
    assert exsitu.member_readings(cfg, mix, ref, eps,
                                  ref + 1.5 * eps).max_err_tol > 1
    # a constant field: exact, with its eps 0
    flat = np.full((4, 4, 4), 2.5, np.float32)
    exact = exsitu.member_readings(cfg, mix, flat, 0.0, flat.copy())
    assert exact.mismatches == 0 and exact.rms_err_eps == 0
    near = np.nextafter(flat, np.float32(3))
    assert exsitu.member_readings(cfg, mix, flat, 0.0, near).mismatches == 1


def test_padding_readers_find_nothing_where_the_program_has_neither():
    obs = harness.Observation(spans=[], counters={"members": 13}, device=None,
                              peaks=None, config={}, traffic={})
    assert registry.reader("pad_ms.compress")(obs) is None
    assert registry.reader("pad_share.compress")(obs) is None
    obs.counters.update(stage1_elements=100, pad_elements=25)
    assert registry.reader("pad_share.compress")(obs) == 25.0
