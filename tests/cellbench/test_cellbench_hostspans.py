"""The split of device idle time by program span (``cellbench/hostspans.py``),
the per-layer readers of the program's copy, store and wait spans, and the
readings of the accepted metrics on the two traces recorded on the chip,
which these additions leave as they were."""
from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

from cellbench_testlib import run_small

from cellbench import devtrace, harness, hostspans, registry

DATA = os.path.join(os.path.dirname(__file__), "data")
MARKED = [(hostspans.MARK, 1)]


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def _span(name, start, dur):
    return _ev(name, start, dur, MARKED)


def _trace(threads, ops, devices=1):
    planes = [NS(name="/host:CPU", lines=[NS(name=f"t{i}", events=evs)
                                          for i, evs in enumerate(threads)])]
    for i in range(devices):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[]),
            NS(name="XLA Ops", events=ops)]))
    by_name = {p.name: p for p in planes}
    return NS(planes=planes, find_plane_with_name=by_name.get)


def _observation(spans=(), counters=None, device=None):
    return harness.Observation(list(spans), counters or {}, device, None,
                               {"block": 32}, {})


# -- attribution on synthetic intervals --------------------------------------

def test_nested_spans_give_idle_time_to_the_innermost():
    idle = [(0, 100)]
    spans = [(0, 100, "append"), (20, 60, "stage1"), (30, 50, "copy.to_host")]
    total, per_gap = hostspans.attribute(idle, spans)
    assert total == {"append": 60, "stage1": 20, "copy.to_host": 20}
    assert per_gap == [total]


def test_overlapping_spans_on_threads_give_time_to_the_newest():
    # two worker threads' encodes overlap; the one opened last is innermost
    idle = [(0, 100)]
    spans = [(10, 70, "encode"), (40, 90, "store.write")]
    total, _ = hostspans.attribute(idle, spans)
    assert total == {"host": 20, "encode": 30, "store.write": 50}


def test_time_with_no_span_open_is_the_hosts():
    idle = [(0, 10), (20, 40), (50, 60)]
    spans = [(25, 30, "blockify")]
    total, per_gap = hostspans.attribute(idle, spans)
    assert total == {"host": 35, "blockify": 5}
    assert per_gap == [{"host": 10}, {"host": 15, "blockify": 5},
                       {"host": 10}]
    assert hostspans.attribute(idle, []) == ({"host": 40},
                                             [{"host": 10}, {"host": 20},
                                              {"host": 10}])


def test_busy_time_is_not_attributed():
    idle = [(0, 10), (30, 40)]
    spans = [(0, 40, "encode"), (5, 35, "copy.to_host")]
    total, per_gap = hostspans.attribute(idle, spans)
    assert total == {"encode": 10, "copy.to_host": 10}
    assert per_gap == [{"encode": 5, "copy.to_host": 5},
                       {"copy.to_host": 5, "encode": 5}]


def test_reduction_of_a_marked_trace():
    """Only marked events are program spans; the window clips them, idle
    time is what no chip's operation covers, and the gaps are named by the
    span that holds most of each."""
    main = [_ev(devtrace.WINDOW, 100, 1000), _ev("cb.append", 100, 1000),
            _span("append", 50, 600),              # clipped to [100, 650)
            _span("copy.to_host", 150, 100),
            _ev("Transpose::ExecuteChunk", 300, 50),   # runtime: no mark
            _span("store.commit", 900, 100)]
    worker = [_span("encode", 400, 220)]
    ops = [_ev("%a", 120, 30), _ev("%b", 700, 100), _ev("%c", 1050, 100)]
    split = hostspans.reduce(_trace([main, worker], ops, devices=2))
    # idle [100,120) [150,700) [800,1050) = 820 ns
    assert split.window_s == pytest.approx(1000e-9)
    assert split.idle_s == pytest.approx(820e-9)
    assert split.spans == 4
    want = {"append": 20 + 150 + 30, "copy.to_host": 100, "encode": 220,
            "host": 50 + 100 + 50, "store.commit": 100}
    assert split.by_span == pytest.approx({k: v * 1e-9
                                           for k, v in want.items()})
    assert split.unattributed_share == pytest.approx(200 / 820)
    assert [n for n, _ in split.gaps] == ["encode", "host", "append"]
    assert sum(g for _, g in split.gaps) == pytest.approx(split.idle_s)


def test_reduction_without_program_spans_window_or_device():
    ops = [_ev("%a", 0, 5)]
    unmarked = [_ev(devtrace.WINDOW, 0, 100), _ev("encode", 10, 20)]
    assert hostspans.reduce(_trace([unmarked], ops)) is None
    assert hostspans.reduce(_trace([[_span("encode", 0, 10)]], ops)) is None
    t = _trace([[_ev(devtrace.WINDOW, 0, 100), _span("encode", 0, 10)]], ops)
    t.planes = [p for p in t.planes if not p.name.startswith("/device")]
    assert hostspans.reduce(t) is None


def test_cli_prints_the_split(monkeypatch, capsys):
    split = hostspans.Split(window_s=1.0, idle_s=0.5,
                            by_span={"encode": 0.4, "host": 0.1},
                            gaps=[("encode", 0.3), ("host", 0.1)], spans=7)
    monkeypatch.setattr(hostspans, "load", lambda d: split)
    assert hostspans.main(["somewhere", "--top", "1"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("window 1.0 s, device idle 0.5 s, 7 program")
    assert out[2].split()[:2] == ["encode", "0.4"]
    assert out[-1].split()[:2] == ["encode", "0.3"] and len(out) == 6
    monkeypatch.setattr(hostspans, "load", lambda d: None)
    assert hostspans.main(["somewhere"]) == 1


def test_profile_dir_from_the_observation_or_the_run(tmp_path):
    obs = _observation()
    assert hostspans.profile_dir(obs) is None
    ctx = harness.Context("insitu_wavelet", {}, {}, 1, 1.0, True, 0.0,
                          str(tmp_path))

    def reader_called_by_the_harness(ctx, obs):
        return hostspans.profile_dir(obs)

    assert reader_called_by_the_harness(ctx, obs) == ctx.profile_dir
    obs.profile_dir = "elsewhere"
    assert reader_called_by_the_harness(ctx, obs) == "elsewhere"


# -- the accepted readings on the recorded chip traces ------------------------

@pytest.mark.parametrize("name, kernel, idle, roofline, gap", [
    ("insitu_wavelet", "wavelet_forward", 93.3526258367296,
     6.404996393301322, ["cb.append", 0.331687726]),
    ("serve_zipf_boxes", "wavelet_inverse", 97.11320654380081,
     2.8574303081718373, ["cb.query", 0.023504316]),
])
def test_recorded_traces_read_as_before(name, kernel, idle, roofline, gap):
    """The readings the accepted metrics gave on these traces before the
    program's spans reached the profiler; a trace without program spans
    gives no split, so the new readers stay silent on it."""
    from jax.profiler import ProfileData

    pdata = ProfileData.from_file(os.path.join(DATA, f"{name}.xplane.pb"))
    dt = devtrace.reduce(pdata)
    counters = {"members": 3, "raw_bytes": 12 * 256 ** 3,
                "kernel_elements": {kernel: 3 * 256 ** 3}}
    obs = harness.Observation([], counters, dt,
                              registry.peaks("TPU v5 lite"), {"block": 32}, {})
    read = {m["name"]: registry.reader(m["name"])(obs)
            for m in registry.benchmark()["per_layer"]}
    assert read.pop("device_idle.compress") == idle
    assert read.pop("device_idle.serve") == idle
    assert read.pop(f"{kernel}_roofline") == roofline
    assert set(read.values()) == {None}
    b = devtrace.breakdown(dt)
    assert b["idle_gaps"][0] == gap and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0] in ("jit_step", f"jit_{kernel}")
    assert hostspans.reduce(pdata) is None


# -- the readers on the program's spans ----------------------------------------

def test_readers_stay_silent_without_the_spans():
    """A program without the copy, store and wait spans (the parent of
    this reader) gives no value, and no error."""
    old = [{"name": n, "ph": "X", "dur": 5.0, "args": {}}
           for n in ("stage1", "encode", "serve.query", "serve.flight.wait")]
    obs = _observation(old, {"members": 3, "raw_bytes": 100})
    for name in ("store_ms.compress", "host_copy_ms.compress",
                 "copy_bytes_per_raw.compress", "reader_wait_ms.serve",
                 "idle_unattributed.compress"):
        assert registry.reader(name)(obs) is None


def test_readers_by_hand():
    def ev(name, dur_us, **args):
        return {"name": name, "ph": "X", "dur": dur_us, "args": args}

    spans = [ev("store.write", 1000.0, bytes=10), ev("store.write", 500.0),
             ev("store.commit", 1500.0, t=0),
             ev("copy.to_host", 2000.0, bytes=400),
             ev("copy.to_device", 1000.0, bytes=300),
             ev("reader.wait", 300.0), ev("reader.wait", 100.0),
             ev("serve.flight.wait", 200.0),
             ev("serve.query", 5000.0), ev("serve.query", 5000.0)]
    obs = _observation(spans, {"members": 2, "raw_bytes": 200})
    assert registry.reader("store_ms.compress")(obs) == pytest.approx(1.5)
    assert registry.reader("host_copy_ms.compress")(obs) == pytest.approx(1.5)
    assert registry.reader("copy_bytes_per_raw.compress")(obs) == 3.5
    assert registry.reader("reader_wait_ms.serve")(obs) == pytest.approx(0.3)


def _wavelet_ratio():
    from repro.core import wavelets

    c = wavelets.coarse_side(32, None)
    # field to host, blocks to device, coefficients and a byte mask back,
    # and the coarse corner
    return 1 + 1 + 1 + 0.25 + (c / 32) ** 3


@pytest.mark.parametrize("workload, ratio", [
    ("insitu_wavelet", _wavelet_ratio),
    ("insitu_zfpx", lambda: 3 + 1 / 64),    # q int32, one int32 exponent a cell
    ("insitu_lorenzo", lambda: 3.0),        # int32 residuals
])
def test_traced_run_reads_the_copies_by_their_shapes(workload, ratio,
                                                      tmp_path):
    r = run_small(workload, trace=True, workdir=tmp_path)
    assert r["correct"], r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["copy_bytes_per_raw.compress"] == pytest.approx(ratio(),
                                                             rel=1e-3)
    assert m["store_ms.compress"] > 0 and m["host_copy_ms.compress"] > 0
    assert "idle_unattributed.compress" not in m   # the CPU has no device plane


def test_traced_serve_run_reads_the_waits(tmp_path):
    r = run_small("serve_zipf_boxes", trace=True, workdir=tmp_path)
    assert r["correct"], r["checks"]
    assert r["metrics"]["reader_wait_ms.serve"]["value"] >= 0
