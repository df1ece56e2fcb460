"""The harness's refusals, and the reduction of profiler traces: on
hand-made traces, and on small traces recorded through the harness on a
TPU v5e and kept in ``data/``."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from cellbench_testlib import ROOT

from cellbench import devtrace, run

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_refuses_a_run_without_a_tpu(capsys):
    rc = run.main(["--workload", "insitu_wavelet", "--seed", str(2 ** 33),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "cellbench", tmp_path / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-m", "cellbench.run", "--workload",
                        "insitu_wavelet", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _trace(host_events, ops, modules, devices=1):
    planes = [NS(name="/host:CPU", lines=[NS(name="python",
                                             events=host_events)])]
    for i in range(devices):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=ops)]))
    planes.append(NS(name="/device:TPU:0 SparseCore", lines=[
        NS(name="XLA Ops", events=[_ev("ignored", 0, 10 ** 9)])]))
    by_name = {p.name: p for p in planes}
    return NS(planes=planes, find_plane_with_name=by_name.get)


def test_reduction_by_hand():
    host = [_ev("cb.window", 100, 1000),
            _ev("cb.solver_step", 100, 200),
            _ev("cb.append", 300, 800),
            _ev("unrelated", 0, 5000)]
    # ops overlap (merged), one starts before the window (clipped), one
    # ends after it (clipped)
    ops = [_ev("%a", 50, 100), _ev("%b", 140, 60), _ev("%c", 180, 40),
           _ev("%d", 600, 100), _ev("%e", 1050, 200)]
    modules = [_ev("jit_step(123)", 50, 170), _ev("jit_wavelet_forward(9)",
                                                   600, 100),
               _ev("jit_wavelet_forward(9)", 1050, 200)]
    dt = devtrace.reduce(_trace(host, ops, modules))
    # busy [100,220) + [600,700) + [1050,1100) = 270 ns of a 1000 ns window
    assert dt.window_s == pytest.approx(1000e-9)
    assert dt.busy_s == pytest.approx(270e-9)
    assert dt.idle_share == pytest.approx(0.73)
    assert dt.program_seconds("wavelet_forward") == pytest.approx(150e-9)
    assert dt.program_s["jit_step"] == pytest.approx(120e-9)
    # gaps [220,600) append 300-600 > step 220-300; [700,1050) append
    assert dt.gaps == [("cb.append", pytest.approx(380e-9)),
                       ("cb.append", pytest.approx(350e-9))]
    assert sum(g for _, g in dt.gaps) + dt.busy_s == pytest.approx(dt.window_s)
    b = devtrace.breakdown(dt, top=1)
    assert b["device_ops"] == [["jit_wavelet_forward", pytest.approx(150e-9)]]
    assert len(b["idle_gaps"]) == 1


def test_reduction_averages_busy_over_chips():
    host = [_ev("cb.window", 0, 1000)]
    dt = devtrace.reduce(_trace(host, [_ev("%a", 0, 500)], [], devices=4))
    assert dt.busy_s == pytest.approx(500e-9)
    assert dt.gaps == [("host", pytest.approx(500e-9))]


def test_reduction_without_window_or_device():
    assert devtrace.reduce(_trace([], [_ev("%a", 0, 5)], [])) is None
    host = [_ev("cb.window", 0, 10)]
    t = _trace(host, [], [])
    t.planes = [p for p in t.planes if not p.name.startswith("/device")]
    assert devtrace.reduce(t) is None


@pytest.mark.parametrize("name, kernel, span", [
    ("serve_zipf_boxes", "wavelet_inverse", "cb.query"),
    ("insitu_wavelet", "wavelet_forward", "cb.append"),
])
def test_reduction_of_chip_traces(name, kernel, span):
    from jax.profiler import ProfileData

    path = os.path.join(DATA, f"{name}.xplane.pb")
    dt = devtrace.reduce(ProfileData.from_file(path))
    assert dt is not None
    assert 0 < dt.busy_s < dt.window_s
    assert 0 < dt.idle_share < 1
    assert dt.program_seconds(kernel) > 0
    assert sum(g for _, g in dt.gaps) + dt.busy_s == pytest.approx(
        dt.window_s, rel=1e-6)
    assert span in {n for n, _ in dt.gaps}
    assert {n for n, _ in dt.gaps} <= {"cb.query", "cb.append",
                                       "cb.solver_step", "host"}


def test_peak_bytes_counts_program_reservations(monkeypatch):
    """The peak of the fullest chip: arrays plus what the runtime reserves
    for compiled programs' temporaries, as a TPU reports them apart."""
    import jax

    from cellbench import harness

    def dev(stats):
        return NS(memory_stats=lambda: stats)

    chips = [dev({"peak_bytes_in_use": 1_349_257_216,
                  "peak_bytes_reserved": 6_414_352_384}),
             dev({"peak_bytes_in_use": 7_000_000_000}), dev(None)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    assert harness.peak_bytes() == 1_349_257_216 + 6_414_352_384
    monkeypatch.setattr(jax, "local_devices", lambda: chips[2:])
    assert harness.peak_bytes() == 0
