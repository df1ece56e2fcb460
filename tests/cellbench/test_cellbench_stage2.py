"""The ``stage2`` span, one per member around the drain of its chunks, and
``stage2_wall_ms.compress``, its per-layer reader."""
from __future__ import annotations

import numpy as np
import pytest

from cellbench import harness, registry
from repro.core import CompressionSpec, Pipeline
from repro.obs import trace

READ = registry.reader("stage2_wall_ms.compress")


def _ev(name, dur_us, **args):
    return {"name": name, "ph": "X", "dur": dur_us, "args": args}


def _observation(spans):
    return harness.Observation(spans, {"members": 2}, None, None, {}, {})


def test_reads_the_drain_per_member():
    spans = [_ev("stage1", 100.0), _ev("stage1", 100.0),
             _ev("encode", 4000.0), _ev("encode", 4000.0),
             _ev("stage2", 3000.0, workers=4, chunks=16),
             _ev("stage2", 1000.0, workers=4, chunks=16)]
    assert READ(_observation(spans)) == pytest.approx(2.0)


def test_silent_without_the_span():
    """The program before the span gives no value, and no error."""
    old = [_ev("stage1", 100.0), _ev("encode", 4000.0),
           _ev("store.write", 10.0)]
    assert READ(_observation(old)) is None
    assert READ(_observation([])) is None


def _stage2_spans(blocks, spec, **kw):
    trace.reset()
    trace.enable()
    try:
        chunks = list(Pipeline(spec, workers=kw.pop("workers", 1))
                      .iter_chunks(blocks, **kw))
    finally:
        trace.disable()
    events = [e for e in trace.TRACER.events() if e["ph"] == "X"]
    trace.reset()
    return chunks, events


@pytest.mark.parametrize("nblocks, workers, used", [
    (8, 1, 1),      # serial
    (8, 4, 4),      # a pool of 4 over 8 chunks
    (8, 64, 8),     # no more threads than chunks
    (1, 4, 1),      # one chunk: encoded serially
])
def test_one_stage2_span_per_call(nblocks, workers, used):
    spec = CompressionSpec(scheme="lorenzo", eps=1e-3, block_size=16,
                           buffer_bytes=4 * 16 ** 3)
    blocks = np.random.default_rng(0).standard_normal(
        (nblocks, 16, 16, 16)).astype(np.float32)
    chunks, events = _stage2_spans(blocks, spec, workers=workers)
    stage2 = [e for e in events if e["name"] == "stage2"]
    assert len(stage2) == 1
    assert stage2[0]["args"] == {"workers": used, "chunks": nblocks}
    encodes = [e for e in events if e["name"] == "encode"]
    assert len(encodes) == len(chunks) == nblocks
    # the drain's wall time covers the chunks' encodes on one thread
    if used == 1:
        (s,) = stage2
        assert all(s["ts"] <= e["ts"] and e["ts"] + e["dur"] <= s["ts"] + s["dur"]
                   for e in encodes)


def test_stage2_span_covers_the_consumer():
    """The span stays open while the consumer handles each chunk, as the
    store's write of it does."""
    spec = CompressionSpec(scheme="lorenzo", eps=1e-3, block_size=16,
                           buffer_bytes=4 * 16 ** 3)
    blocks = np.zeros((4, 16, 16, 16), np.float32)
    trace.reset()
    trace.enable()
    try:
        for _chunk in Pipeline(spec, workers=2).iter_chunks(blocks):
            with trace.span("store.write"):
                pass
    finally:
        trace.disable()
    events = {e["name"]: e for e in trace.TRACER.events() if e["ph"] == "X"}
    trace.reset()
    s, w = events["stage2"], events["store.write"]
    assert s["ts"] <= w["ts"] and w["ts"] + w["dur"] <= s["ts"] + s["dur"]
