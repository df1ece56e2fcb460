"""Fields whose shape is not a multiple of the block size, and the
value-range-relative bound, across the stack: blocks, container reader,
dataset, domain decomposition, the HTTP region front, and the byte identity
of members whose shape is a multiple of the block size.

The scheme-by-scheme comparison with the plain padding reference is in
``tests/test_scheme_conformance.py``; rank-count byte identity of a padded
field is in ``tests/test_cluster.py``.
"""
import json
import os
import sys

import numpy as np
import pytest

from repro import obs
from repro.cluster.decompose import decompose, gather, scatter
from repro.core import CompressionSpec, Pipeline, container
from repro.core import blocks as blk
from repro.serve import Client, RegionHTTPServer
from repro.store import CZDataset

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SHAPE, BS = (20, 36, 50), 8
SPEC = CompressionSpec(scheme="lorenzo", eps=1e-3, block_size=BS,
                       buffer_bytes=1 << 13)


def _field(shape=SHAPE, seed=0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (10 + rng.standard_normal(shape)).astype(np.float32)


def _reference_pad(field, bs):
    nb = blk.num_blocks(field.shape, bs)
    return np.pad(field, [(0, b * bs - n) for b, n in zip(nb, field.shape)],
                  mode="edge")


def test_device_pad_is_counted_without_a_span():
    """On a ``jax.Array`` the pad is only dispatched inside ``blockify``:
    it is counted, and no ``pad`` span times the dispatch."""
    import jax.numpy as jnp

    from repro.obs import trace

    pad = obs.REGISTRY.get("cz_blockify_pad_elements_total")
    p0 = pad.value()
    field = _field()
    trace.TRACER.reset()
    trace.enable()
    try:
        got = blk.blockify(jnp.asarray(field), BS)
    finally:
        trace.disable()
    assert pad.value() - p0 == blk.pad_elements(SHAPE, BS)
    assert not [e for e in trace.TRACER.events() if e.get("name") == "pad"]
    np.testing.assert_array_equal(np.asarray(got), blk.blockify(field, BS))


# ---------------------------------------------------------------------------
# core.blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,bs", [((20, 36, 50), 8), ((5, 17, 33), 16),
                                      ((1, 1, 1), 4), ((32, 9, 16), 16)])
@pytest.mark.parametrize("kind", ["numpy", "jax"])
def test_blockify_pads_edge_blocks_and_unblockify_crops(shape, bs, kind):
    import jax.numpy as jnp

    field = _field(shape)
    src = jnp.asarray(field) if kind == "jax" else field
    blocks = blk.blockify(src, bs)
    nb = blk.num_blocks(shape, bs)
    ref = _reference_pad(field, bs).reshape(
        nb[0], bs, nb[1], bs, nb[2], bs).transpose(0, 2, 4, 1, 3, 5)
    np.testing.assert_array_equal(np.asarray(blocks),
                                  ref.reshape(-1, bs, bs, bs))
    back = blk.unblockify(blocks, shape)
    assert isinstance(back, np.ndarray) == (kind == "numpy")
    np.testing.assert_array_equal(np.asarray(back), field)
    assert blk.pad_elements(shape, bs) == \
        int(np.prod(nb)) * bs ** 3 - field.size


def test_blockify_of_a_multiple_shape_is_unchanged():
    field = _field((16, 32, 8))
    blocks = blk.blockify(field, 8)
    expect = field.reshape(2, 8, 4, 8, 1, 8).transpose(0, 2, 4, 1, 3, 5)
    np.testing.assert_array_equal(blocks, expect.reshape(-1, 8, 8, 8))
    assert blk.pad_elements(field.shape, 8) == 0


def test_num_blocks_rounds_up_and_refuses_empty_axes():
    assert blk.num_blocks((100, 500, 500), 32) == (4, 16, 16)
    assert blk.pad_elements((100, 500, 500), 32) == 8_554_432
    with pytest.raises(ValueError, match="empty axis"):
        blk.num_blocks((0, 8, 8), 8)


def test_pad_span_and_counters():
    from repro.obs import trace

    pad = obs.REGISTRY.get("cz_blockify_pad_elements_total")
    stage1 = obs.REGISTRY.get("cz_stage1_elements_total")
    p0, s0 = pad.value(), stage1.value()
    field = _field()
    trace.TRACER.reset()
    trace.enable()
    try:
        Pipeline(SPEC).compress(field)
    finally:
        trace.disable()
    npad = blk.pad_elements(SHAPE, BS)
    assert pad.value() - p0 == npad
    assert stage1.value() - s0 == field.size + npad
    spans = [e for e in trace.TRACER.events() if e.get("name") == "pad"]
    assert len(spans) == 1
    assert spans[0]["args"] == {"elements": field.size + npad,
                                "pad_elements": npad}
    # no padding, no span, nothing counted
    trace.TRACER.reset()
    trace.enable()
    try:
        Pipeline(SPEC).compress(_field((16, 16, 16)))
    finally:
        trace.disable()
    assert not [e for e in trace.TRACER.events() if e.get("name") == "pad"]
    assert pad.value() - p0 == npad


# ---------------------------------------------------------------------------
# container reader at the edges
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def padded_member(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("pad") / "f.cz")
    field = _field()
    container.write_field(path, field, SPEC)
    return path, field


def test_header_records_padding_and_true_raw_bytes(padded_member):
    path, field = padded_member
    with container.FieldReader(path) as r:
        assert r.header["pad"] == "edge"
        assert r.header["raw_bytes"] == field.nbytes
        assert r.header["field_shape"] == list(SHAPE)
        assert r.nb == (3, 5, 7)


@pytest.mark.parametrize("lo,hi", [
    ((0, 0, 0), SHAPE),                 # the whole field
    ((16, 32, 48), SHAPE),              # only the partial corner block
    ((15, 31, 47), (20, 36, 50)),       # straddles into the edge blocks
    ((19, 0, 0), (20, 36, 50)),         # the last plane
    ((3, 35, 2), (17, 36, 49)),         # one row of edge blocks along y
    ((0, 0, 49), (1, 1, 50)),           # one point in an edge block
])
def test_read_box_at_edges_equals_crop_of_read_all(padded_member, lo, hi):
    path, field = padded_member
    with container.FieldReader(path, cache_chunks=4) as r:
        full = r.read_all()
        box = r.read_box(lo, hi)
    assert full.shape == SHAPE
    np.testing.assert_array_equal(
        box, full[tuple(slice(a, b) for a, b in zip(lo, hi))])
    assert np.max(np.abs(full - field)) <= 1e-3 * (1 + 1e-4) + 1e-5


def test_read_box_refuses_boxes_past_the_field(padded_member):
    path, _ = padded_member
    with container.FieldReader(path) as r:
        with pytest.raises(ValueError, match="outside field"):
            r.read_box((0, 0, 0), (20, 36, 51))   # inside the padding


# ---------------------------------------------------------------------------
# domain decomposition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout,ranks", [("slab", 3), ("pencil", 4),
                                          ("brick", 8)])
def test_decompose_non_multiple_shape(layout, ranks):
    shape = (40, 72, 100)
    subs = decompose(shape, ranks, 16, layout)
    assert len(subs) == ranks
    assert sum(s.nvoxels for s in subs) == int(np.prod(shape))
    for s in subs:
        for a, b, n in zip(s.lo, s.hi, shape):
            assert a % 16 == 0 and a < b <= n
            assert b % 16 == 0 or b == n     # the last part takes the rest
    field = _field(shape)
    np.testing.assert_array_equal(gather(scatter(field, subs), subs), field)


# ---------------------------------------------------------------------------
# the HTTP region front at the edges
# ---------------------------------------------------------------------------

def test_http_region_reads_at_edges(tmp_path):
    root = str(tmp_path / "ds")
    field = _field()
    with CZDataset(root, "a", spec=SPEC) as ds:
        ds.append({"p": field})
    boxes = [((16, 32, 48), SHAPE), ((0, 35, 0), (20, 36, 50)),
             ((7, 3, 45), (20, 30, 50))]
    with CZDataset(root) as ds, \
            RegionHTTPServer(root, port=0).start() as srv, \
            Client(srv.url) as c:
        for lo, hi in boxes:
            ref = ds.read_box("p", 0, lo, hi)
            got = c.region("p", 0, lo, hi)
            assert got.shape == tuple(b - a for a, b in zip(lo, hi))
            assert got.tobytes() == ref.tobytes()


# ---------------------------------------------------------------------------
# bound="range"
# ---------------------------------------------------------------------------

def test_range_bound_resolves_per_member_and_records_eps(tmp_path):
    spec = CompressionSpec(scheme="lorenzo", eps=1e-4, bound="range",
                           block_size=BS, buffer_bytes=1 << 13)
    root = str(tmp_path / "ds")
    a = _field(seed=1)
    b = (1000.0 * _field(seed=2)).astype(np.float32)
    with CZDataset(root, "a", spec=spec) as ds:
        ds.append({"a": a, "b": b})
        ds.append({"a": a})
    with open(os.path.join(root, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["spec"]["bound"] == "range"
    assert manifest["spec"]["eps"] == 1e-4
    with CZDataset(root) as ds:
        for q, f in (("a", a), ("b", b)):
            r = ds.reader(q, 0)
            eps = 1e-4 * (float(f.max()) - float(f.min()))
            assert r.header["spec"]["eps"] == eps
            assert "bound" not in r.header["spec"]   # the member is absolute
            assert r.header["scheme_params"]["eps"] == eps
            err = np.max(np.abs(ds.read_field(q, 0).astype(np.float64) - f))
            assert err <= eps * (1 + 1e-4) + np.spacing(np.abs(f).max())
        # the second append resolves from its own field again, once
        assert ds.reader("a", 1).header["spec"]["eps"] == \
            ds.reader("a", 0).header["spec"]["eps"]


def test_range_member_reencoded_is_not_scaled_again(tmp_path):
    spec = CompressionSpec(scheme="lorenzo", eps=1e-3, bound="range",
                           block_size=BS, buffer_bytes=1 << 13)
    root = str(tmp_path / "ds")
    field = _field(seed=3)
    with CZDataset(root, "a", spec=spec) as ds:
        ds.append({"p": field})
        member = ds.reader("p", 0)
        member_spec = member.spec
        dec = ds.read_field("p", 0)
    assert member_spec.bound == "abs"
    again = str(tmp_path / "again.cz")
    container.write_field(again, dec, member_spec)
    with container.FieldReader(again) as r:
        assert r.spec.eps == member_spec.eps


@pytest.mark.parametrize("value", [0.0, 3.25, -1e6])
def test_range_bound_constant_field_decodes_exactly(tmp_path, value):
    spec = CompressionSpec(scheme="lorenzo", eps=1e-4, bound="range",
                           block_size=BS, buffer_bytes=1 << 13)
    field = np.full(SHAPE, value, np.float32)
    with CZDataset(str(tmp_path / "ds"), "a", spec=spec) as ds:
        ds.append({"c": field})
        np.testing.assert_array_equal(ds.read_field("c", 0), field)
        assert ds.reader("c", 0).header["scheme"] == "raw"


def test_range_spec_json_round_trip_and_refusals():
    spec = CompressionSpec(scheme="zfpx", eps=1e-4, bound="range")
    d = spec.to_json()
    assert d["bound"] == "range"
    assert CompressionSpec.from_json(d) == spec
    assert "bound" not in CompressionSpec().to_json()
    assert CompressionSpec.from_json(CompressionSpec().to_json()).bound == "abs"
    with pytest.raises(ValueError, match="unknown bound"):
        CompressionSpec(bound="rel").validate()
    with pytest.raises(ValueError, match="resolve"):
        Pipeline(spec)
    with pytest.raises(ValueError, match="finite"):
        spec.resolve(np.array([[[1.0, np.nan]]], np.float32))
    assert CompressionSpec().resolve(_field()) == CompressionSpec()


# ---------------------------------------------------------------------------
# members whose shape is a multiple of the block size are unchanged
# ---------------------------------------------------------------------------

def _golden():
    sys.path.insert(0, DATA)
    try:
        import make_golden
    finally:
        sys.path.remove(DATA)
    return make_golden


@pytest.mark.parametrize("scheme", ["lorenzo", "zfpx", "wavelet"])
def test_golden_fixture_headers_unchanged(scheme, tmp_path):
    """The committed CZ2 fixtures' footers, written again by today's writer
    from the same field and spec, are the same JSON up to the stage-2
    bytes (chunk sizes and CRCs, which follow the zlib build) and the
    ``format`` they were written under: no ``pad``, no ``bound``, the same
    ``raw_bytes`` and key order."""
    mg = _golden()
    path = str(tmp_path / "g.cz")
    container.write_field(path, mg.golden_field(), mg.spec_for(scheme))

    def footer(p):
        with open(p, "rb") as f:
            header, _ = container._read_header(f)
        for k in ("chunk_sizes", "chunk_crc32", "format"):
            header.pop(k)
        return json.dumps(header)

    assert footer(path) == footer(os.path.join(DATA, f"cz2_{scheme}.cz"))


def test_golden_auto_fixture_rewritten_byte_identical(tmp_path):
    """The newest fixture (format 3, stored chunks) is written again byte
    for byte."""
    mg = _golden()
    path = str(tmp_path / "g.cz")
    container.write_field(path, mg.golden_auto_field(), mg.auto_spec())
    with open(path, "rb") as a, \
            open(os.path.join(DATA, "cz2_auto.cz"), "rb") as b:
        assert a.read() == b.read()


def test_multiple_shape_abs_member_header_is_unchanged(tmp_path):
    field = _field((16, 16, 24))
    with CZDataset(str(tmp_path / "ds"), "a", spec=SPEC, workers=1) as ds:
        ds.append({"p": field}, time=0.5)
        header = ds.reader("p", 0).header
    assert list(header) == [
        "format", "scheme", "scheme_params", "dtype", "spec", "field_shape",
        "raw_bytes", "quantity", "t", "time", "nblocks", "chunk_nblocks",
        "chunk_sizes", "chunk_crc32"]
    assert list(header["spec"]) == [
        "scheme", "wavelet", "eps", "block_size", "levels", "shuffle",
        "zero_bits", "stage2", "buffer_bytes", "precision", "dtype", "device",
        "extra"]
    assert header["raw_bytes"] == field.nbytes
