"""BENCHMARK.json against the benchmark's contract, the registry that finds
its parts by name, and the yardstick's arithmetic: roofline counts worked
by hand, the comparison, the region-key generator and the field
generator."""
from __future__ import annotations

import json
import re
import shutil

import numpy as np
import pytest

from cellbench_testlib import ROOT

from cellbench import check, registry, roofline, solver, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark()


def _one_line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) <= 64 * 1024
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len(bench["command"]) <= 32
    assert all(_one_line(w) for w in bench["command"])


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["why"])
        assert _one_line(c["source"])
        assert c["file"] == f"cellbench/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_workloads_have_their_files(bench):
    pairs = set()
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = registry.traffic(w["traffic"])
        assert registry.driver(mix).run
        limits = registry.limits(w["name"])
        assert set(check.NAMES) <= set(limits)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics(bench, kind):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench[kind]:
        keys = {"name", "unit", "better", "source"}
        keys |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
        assert set(m) - {"workloads"} == keys
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        # every per-layer metric lists its cells; an end-to-end one may not
        if kind == "per_layer" or "workloads" in m:
            assert m["workloads"] and set(m["workloads"]) <= cells
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["moves"] in e2e and _one_line(m["layer"])
            assert (registry.PACKAGE / "layer_metrics"
                    / f"{m['name']}.py").is_file()
            if m["name"].endswith("_roofline"):
                assert m["unit"] == "%"


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in registry.end_to_end(bench, w)]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = registry.per_layer(bench, w)
        assert layer
        for m in layer:   # each per-layer metric moves a metric its cell reports
            assert m["moves"] in e2e


def test_setup_bound(bench):
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


def test_peaks_keyed_by_device_kind():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("TPU v9 imaginary")


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A new per-layer metric, traffic mix and configuration need new files
    and entries only: the registry finds them, and no existing file
    changes."""
    pkg = tmp_path / "cellbench"
    shutil.copytree(registry.PACKAGE, pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    (pkg / "layer_metrics" / "members.insitu.py").write_text(
        "def read(obs):\n    return obs.counters.get('members')\n")
    mix = json.loads((pkg / "traffic" / "dumps_wavelet.json").read_text())
    (pkg / "traffic" / "dumps_wavelet_2step.json").write_text(
        json.dumps({**mix, "steps_per_dump": 2}))
    cfg = json.loads((pkg / "configs" / "cavitation_insitu_256.json")
                     .read_text())
    (pkg / "configs" / "cavitation_insitu_128.json").write_text(
        json.dumps({**cfg, "side": 128}))
    monkeypatch.setattr(registry, "PACKAGE", pkg)

    bench = registry.benchmark()
    cell = {"name": "insitu_wavelet_2step", "config": "cavitation_insitu_128",
            "traffic": "dumps_wavelet_2step", "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    bench["per_layer"].append(
        {"name": "members.insitu", "unit": "members", "better": "higher",
         "source": "program_counter", "layer": "store",
         "moves": "compress_GBps", "workloads": [cell["name"]]})
    assert registry.cell(bench, cell["name"]) is cell
    assert registry.config(cell["config"])["side"] == 128
    assert registry.traffic(cell["traffic"])["steps_per_dump"] == 2
    assert "members.insitu" in [m["name"] for m in
                                registry.per_layer(bench, cell)]
    read = registry.reader("members.insitu")
    assert read(type("Obs", (), {"counters": {"members": 6}})()) == 6
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_roofline_counts_by_hand():
    # one 32^3 block: 3 levels (sides 32, 16, 8), 12 operations per element
    # and level on a cube whose volume falls 8-fold per level
    assert roofline.wavelet_levels(32) == 3
    assert roofline.wavelet_levels(16) == 2
    assert roofline.wavelet_levels(8) == 1
    ops, nbytes = roofline.wavelet_forward(32 ** 3)
    assert ops == 32 ** 3 * 12 * (1 + 1 / 8 + 1 / 64)
    assert ops == 448512
    assert nbytes == 262144
    assert roofline.wavelet_inverse(32 ** 3) == (ops, nbytes)
    ops, nbytes = roofline.zfpx_encode(32 ** 3)
    assert ops == 655360
    assert nbytes == 32 ** 3 * 8 + 512 * 4   # 512 cells of 4^3, int32 exponent
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    t, bound = roofline.least_time("wavelet_forward", 256 ** 3, peaks)
    assert bound == "memory" and t == pytest.approx(256 ** 3 * 8 / 819e9)
    t, bound = roofline.least_time("zfpx_encode", 10, {"hbm_bytes_per_s": 1e12,
                                                       "flops_per_s": 1.0})
    assert bound == "compute" and t == 200.0


def test_roofline_share_from_an_observation():
    dt = type("DT", (), {"program_seconds": lambda self, k: 2e-3})()
    obs = type("Obs", (), {
        "counters": {"kernel_elements": {"wavelet_forward": 256 ** 3}},
        "device": dt, "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
        "config": {"block": 32}})()
    assert roofline.share(obs, "wavelet_forward") == pytest.approx(
        100 * 256 ** 3 * 8 / 819e9 / 2e-3)
    assert roofline.share(obs, "zfpx_encode") is None


def test_tolerance_and_readings():
    ref = np.full((4, 4, 4), 8.0, np.float32)
    tol = check.tolerance(0.1, ref)
    assert tol == pytest.approx(0.1 * (1 + 1e-4) + float(np.spacing(np.float32(8))))
    r = check.Readings(bound=0.1, eps=1e-3)
    ans = ref.copy()
    ans[0, 0, 0] += 0.05
    r.add(ans, ref)
    assert r.max_err_tol == pytest.approx(0.05 / tol, rel=1e-5)
    assert r.rms_err_eps == pytest.approx(np.sqrt(0.05 ** 2 / 64) / 1e-3,
                                          rel=1e-5)
    r.add(None, ref)
    r.add(ref[:2], ref)
    r.add(np.full_like(ref, np.nan), ref)
    assert r.mismatches == 3 and r.compared == 4


def test_judge_needs_every_limit():
    values = {"max_err/tol": 0.5, "rms_err/eps": 2.0, "mismatches": 0}
    limits = {"max_err/tol": {"limit": 1.0}, "rms_err/eps": {"limit": 3.0},
              "mismatches": {"limit": 0}}
    assert check.correct(check.judge(values, limits))
    assert not check.correct(check.judge({**values, "mismatches": 1}, limits))
    with pytest.raises(KeyError):
        check.judge(values, {"max_err/tol": {"limit": 1.0}})


def test_bfloat16_control_rounds():
    ref = np.linspace(7.0, 9.0, 1000, dtype=np.float32)
    low = check.bfloat16(ref)
    assert 0 < np.abs(low - ref).max() <= 2.0 ** -5


def test_zipf_boxes_streams():
    """Each client's stream is pure in (seed, client), holds the mix's
    side shares exactly in every round, and follows the Zipf law."""
    mix = registry.traffic("zipf_boxes_closed8")
    gen = traffic.ZipfBoxes(mix, 256, 4, ["p", "rho", "E"])
    again = traffic.ZipfBoxes(mix, 256, 4, ["p", "rho", "E"])

    def take(g, seed, client, n=600):
        it = g.stream(seed, client)
        return [next(it) for _ in range(n)]

    a = take(gen, SEED_BIG, 0)
    assert a == take(again, SEED_BIG, 0)
    assert a != take(gen, SEED_BIG, 1) and a != take(gen, SEED_BIG + 1, 0)
    for t, q, lo, hi in a:
        assert 0 <= t < 4 and q in ("p", "rho", "E")
        assert all(0 <= x < y <= 256 for x, y in zip(lo, hi))
        assert len({y - x for x, y in zip(lo, hi)}) == 1
    for seed in (1, SEED_BIG, 2 ** 31 - 5):
        sides = [hi[0] - lo[0] for _, _, lo, hi in take(gen, seed, 3, 200)]
        for r in range(0, 200, 10):
            one = sides[r:r + 10]
            assert [one.count(s) for s in (32, 64, 128)] == [6, 3, 1]
    # the hottest key of the smallest side comes back as often as Zipf says
    keys = [(t, q, lo) for t, q, lo, hi in a if hi[0] - lo[0] == 32]
    top = gen.keys[0][0]
    want = traffic.zipf_weights(len(gen.keys[0]), mix["zipf_s"])[0]
    assert keys.count(top) / len(keys) == pytest.approx(want, abs=0.04)


SEED_BIG = 2 ** 32 + 7


def test_initial_state_same_blocks_other_order():
    """Seeds translate the cloud by whole blocks along the first axis: the
    same state, rolled."""
    cfg = {**registry.config("cavitation_insitu_256"), "side": 64, "block": 16}
    a = np.asarray(solver.initial_state(cfg, 1))
    assert a.shape == (5, 64, 64, 64)
    np.testing.assert_array_equal(a, np.asarray(solver.initial_state(cfg, 1)))
    seeds = [s for s in range(2, 50)
             if solver.placement(s, 64, 16, "blocks").any()]
    shift = solver.placement(seeds[0], 64, 16, "blocks")
    assert shift[0] % 16 == 0 and not shift[1:].any()
    b = np.asarray(solver.initial_state(cfg, seeds[0]))
    base = solver.placement(1, 64, 16, "blocks")
    rolled = np.roll(a, tuple(int(v) for v in shift - base), axis=(1, 2, 3))
    np.testing.assert_array_equal(b, rolled)


def test_initial_state_cells_placement():
    """With placement "cells" a seed moves the cloud by any cell offset
    along every axis: other data in every block, the same cloud rolled."""
    cfg = {**registry.config("cavitation_insitu_256"), "side": 64, "block": 16}
    still = next(s for s in range(100)
                 if not solver.placement(s, 64, 16, "blocks").any())
    a = np.asarray(solver.initial_state(cfg, still))
    cells = {**cfg, "placement": "cells"}
    shifts = [solver.placement(s, 64, 16, "cells") for s in (3, SEED_BIG)]
    assert any(v % 16 for sh in shifts for v in sh)
    for seed, shift in zip((3, SEED_BIG), shifts):
        b = np.asarray(solver.initial_state(cells, seed))
        np.testing.assert_array_equal(
            b, np.roll(a, tuple(int(v) for v in shift), axis=(1, 2, 3)))
    with pytest.raises(ValueError):
        solver.placement(3, 64, 16, "anywhere")
