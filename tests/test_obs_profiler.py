"""Program spans on the profiler's clock: with the process tracer on, each
``repro.obs.trace`` span also opens a ``jax.profiler.TraceAnnotation``,
so it lands on the host plane of the same trace as the device's
operations.  Also the spans of the copies between host and device, the
store's writes and commits, and the wait for a reader's lock."""
import glob
import os
import subprocess
import sys
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import context as obs_context
from repro.obs import trace as obs_trace

BS = 8


@pytest.fixture(autouse=True)
def _tracer_off():
    obs_trace.disable()
    obs_trace.reset()
    yield
    obs_trace.disable()
    obs_trace.reset()


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` and logs its use."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kwargs):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name, kwargs))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


def _by_name(name):
    return [e for e in obs.TRACER.events() if e["name"] == name]


def test_disabled_span_is_the_shared_null():
    assert obs_trace.span("encode", chunk=1) is obs_trace._NULL
    assert obs_trace.Tracer().span("x") is obs_trace._NULL
    with obs_context.request(collect=False):
        assert obs_trace.span("fetch") is obs_trace._NULL
    with obs_trace.span("encode") as sp:
        sp.set(bytes=3)          # a no-op on the null span
    assert obs.TRACER.events() == []


def test_enabled_span_opens_an_annotation_and_records_the_same_event(
        monkeypatch):
    anns = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    obs_trace.enable()
    with obs_trace.span("encode", chunk=3, scheme="zfpx") as sp:
        assert anns.log == [("enter", "encode",
                             {obs_trace.PROFILER_MARK: 1})]
        sp.set(raw_bytes=8, encoded_bytes=2)
    assert anns.log[-1] == ("exit", "encode")
    (ev,) = obs.TRACER.events()
    assert set(ev) == {"name", "ph", "cat", "ts", "dur", "pid", "tid", "args"}
    assert (ev["name"], ev["ph"], ev["cat"]) == ("encode", "X", "repro")
    assert ev["args"] == {"chunk": 3, "scheme": "zfpx", "raw_bytes": 8,
                          "encoded_bytes": 2}
    assert ev["dur"] >= 0


def test_collecting_context_alone_opens_no_annotation(monkeypatch):
    """Tail sampling keeps every serve request's spans with the tracer off;
    those spans do not touch the profiler."""
    anns = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    with obs_context.request(collect=True) as ctx:
        with obs_trace.span("fetch", chunk=0) as sp:
            sp.set(bytes=5)
    assert anns.log == []
    assert [e["name"] for e in ctx.events] == ["fetch"]
    assert ctx.events[0]["args"]["bytes"] == 5


def test_spans_land_on_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    obs_trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("append"):
            with obs_trace.span("copy.to_host", bytes=4):
                np.asarray(jnp.ones(4).sum())
        worker = threading.Thread(target=lambda: obs_trace.span(
            "encode").__enter__().__exit__(None, None, None))
        worker.start()
        worker.join()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    marked = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)   # the stats type
        for i, line in enumerate(host.lines):
            for ev in line.events:
                if (obs_trace.PROFILER_MARK, 1) in list(ev.stats):
                    marked[ev.name] = (i, ev.start_ns, ev.duration_ns)
    assert set(marked) == {"append", "copy.to_host", "encode"}
    a, c = marked["append"], marked["copy.to_host"]
    assert a[0] == c[0] != marked["encode"][0]     # the worker's own thread
    assert a[1] <= c[1] and c[1] + c[2] <= a[1] + a[2]


def test_span_works_before_jax_is_imported():
    code = ("import sys; from repro.obs import trace; trace.enable()\n"
            "with trace.span('x') as sp: sp.set(bytes=1)\n"
            "assert 'jax' not in sys.modules\n"
            "assert trace.TRACER.events()[0]['args'] == {'bytes': 1}\n")
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.environ.get("PYTHONPATH", "")])}
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


def _field(n=32, seed=5):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, n, n)).astype(np.float32)


def test_encode_decode_fetch_keep_their_names_and_args(tmp_path):
    from repro.core import CompressionSpec, container

    spec = CompressionSpec(scheme="zfpx", block_size=BS, eps=1e-2,
                           buffer_bytes=1 << 13)
    path = str(tmp_path / "f.cz")
    obs_trace.enable()
    container.write_compressed(path, _field(), spec)
    with container.FieldReader(path) as r:
        r.fetch_chunk(0)
        r.fetch_chunk(0)          # a hit: no fetch, no decode
    enc, dec, fetch = _by_name("encode"), _by_name("decode"), _by_name("fetch")
    assert len(enc) > 1 and len(dec) == 1 and len(fetch) == 1
    assert list(enc[0]["args"]) == ["chunk", "scheme", "raw_bytes",
                                    "encoded_bytes", "ratio"]
    assert enc[0]["args"]["scheme"] == "zfpx"
    assert enc[0]["args"]["raw_bytes"] == spec.buffer_bytes   # 4 blocks a chunk
    assert list(dec[0]["args"]) == ["scheme", "nblocks", "encoded_bytes"]
    assert list(fetch[0]["args"]) == ["chunk", "bytes"]
    assert fetch[0]["args"]["bytes"] == dec[0]["args"]["encoded_bytes"]
    # the store's writes: one a chunk, one for the footer and its fsync
    writes = _by_name("store.write")
    assert len(writes) == len(enc) + 1
    assert sum(w["args"]["bytes"] for w in writes[:-1]) == \
        sum(e["args"]["encoded_bytes"] for e in enc)
    assert _by_name("blockify")[0]["args"]["bytes"] == _field().nbytes


def test_copy_spans_count_device_bytes_only():
    from repro.core.schemes import to_device, to_host

    obs_trace.enable()
    host = np.ones((4, 8), np.float32)
    dev = jnp.ones((3, 8), jnp.int32)
    x = to_device(host, jnp.float32)
    assert isinstance(x, jax.Array)
    assert to_device(x, jnp.float32) is not None     # already there: no span
    a, b = to_host(dev, host)
    assert isinstance(a, np.ndarray) and b is host
    (same,) = to_host(host)                          # nothing to copy
    assert same is host
    (up,) = _by_name("copy.to_device")
    (down,) = _by_name("copy.to_host")
    assert up["args"] == {"bytes": host.nbytes}
    assert down["args"] == {"bytes": dev.nbytes}


def test_dataset_append_copies_commits_and_writes(tmp_path):
    from repro.core import CompressionSpec
    from repro.store import CZDataset

    spec = CompressionSpec(scheme="lorenzo", block_size=BS, eps=1e-2,
                           device="jax")
    field = jnp.asarray(_field(16))
    obs_trace.enable()
    with CZDataset(str(tmp_path / "ds"), mode="a", spec=spec) as ds:
        ds.append({"p": field, "rho": field})
    to_host = _by_name("copy.to_host")
    # per member: the field, then stage 1's residuals
    assert [e["args"]["bytes"] for e in to_host] == [field.nbytes] * 4
    assert [e["args"]["bytes"] for e in _by_name("copy.to_device")] == \
        [field.nbytes] * 2
    (commit,) = _by_name("store.commit")
    assert commit["args"] == {"t": 0}
    assert {w["args"]["fsync"] for w in _by_name("store.write")
            if "fsync" in w["args"]} == {True}


def test_two_threads_on_one_reader_wait_for_its_lock(tmp_path, monkeypatch):
    from repro.core import CompressionSpec, container

    spec = CompressionSpec(scheme="raw", block_size=BS,
                           buffer_bytes=4 * BS ** 3)
    path = str(tmp_path / "f.cz")
    container.write_compressed(path, _field(), spec)
    reader = container.FieldReader(path)
    assert reader.nchunks >= 2
    get = reader.store.get

    def slow_get(*a, **k):
        time.sleep(0.05)
        return get(*a, **k)

    monkeypatch.setattr(reader.store, "get", slow_get)
    obs_trace.enable()
    threads = [threading.Thread(target=reader.fetch_chunk, args=(ci,))
               for ci in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reader.close()
    waits = _by_name("reader.wait")
    assert sorted(w["args"]["chunk"] for w in waits) == [0, 1]
    # the second thread waited out the first one's slow get
    assert max(w["dur"] for w in waits) > 0.02 * 1e6
