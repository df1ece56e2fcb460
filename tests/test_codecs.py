"""Codec pipeline tests: roundtrips, error bounds, CR sanity, container IO."""
import os

import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip cleanly on a bare interpreter
    from _hypothesis_compat import given, settings, st

from repro.core import (
    CompressionSpec,
    analyze_field,
    compress_field,
    decompress_field,
)
from repro.core import container, fpzipx, szx, zfpx
from repro.core import shuffle as shuf
from repro.core import threshold as th


def smooth_field(n=64, seed=0, noise=0.0):
    rng = np.random.default_rng(seed)
    g = np.mgrid[0:n, 0:n, 0:n].astype(np.float32)
    f = np.full((n, n, n), 100.0, np.float32)
    for _ in range(8):
        c = rng.uniform(8, n - 8, 3)
        r = rng.uniform(3, 7)
        d = np.sqrt(((g - c[:, None, None, None]) ** 2).sum(0))
        f += -60.0 / (1 + np.exp((d - r) * 2.0))
    if noise:
        f += rng.standard_normal((n, n, n)).astype(np.float32) * noise
    return f


FIELD = smooth_field()


def _ulp(x):
    """One fp32 ulp at the field's max magnitude (irreducible storage error)."""
    return float(np.spacing(np.float32(np.max(np.abs(x)))))


@pytest.mark.parametrize(
    "spec",
    [
        CompressionSpec(scheme="wavelet", wavelet=w, eps=1e-3)
        for w in ("w4i", "w4l", "w3ai")
    ]
    + [
        CompressionSpec(scheme="zfpx", eps=1e-3),
        CompressionSpec(scheme="szx", eps=1e-3),
        CompressionSpec(scheme="fpzipx", precision=32),
        CompressionSpec(scheme="fpzipx", precision=16),
        CompressionSpec(scheme="raw"),
        CompressionSpec(scheme="wavelet", shuffle="bit"),
        CompressionSpec(scheme="wavelet", shuffle="none", stage2="lzma"),
        CompressionSpec(scheme="wavelet", zero_bits=8),
        CompressionSpec(scheme="wavelet", stage2="bz2", block_size=16),
        CompressionSpec(scheme="szx", eps=1e-2, block_size=8),
    ],
)
def test_roundtrip_all_schemes(spec):
    comp = compress_field(FIELD, spec)
    dec = decompress_field(comp)
    assert dec.shape == FIELD.shape
    assert np.isfinite(dec).all()
    if spec.scheme == "raw" or (spec.scheme == "fpzipx" and spec.precision == 32):
        np.testing.assert_array_equal(dec, FIELD)
    elif spec.scheme == "szx":
        assert np.max(np.abs(dec - FIELD)) <= spec.eps * (1 + 1e-4) + _ulp(FIELD)
    else:
        assert np.max(np.abs(dec - FIELD)) < 1.0  # lossy but bounded


def test_lossless_fpzipx_bit_exact_weird_values():
    x = np.array(
        [0.0, -0.0, 1.5, -1.5, 1e-38, -1e38, np.pi, 2**-126, 3.4e38],
        np.float32,
    )
    field = np.tile(x, 8 * 8 * 8 // 8 * 8)[: 8**3].reshape(8, 8, 8)
    spec = CompressionSpec(scheme="fpzipx", precision=32, block_size=8)
    dec = decompress_field(compress_field(field, spec))
    np.testing.assert_array_equal(dec.view(np.uint32), field.view(np.uint32))


def test_szx_error_bound_property():
    for eps in (1e-4, 1e-3, 1e-2, 1e-1):
        spec = CompressionSpec(scheme="szx", eps=eps)
        r = analyze_field(FIELD, spec)
        assert r["max_err"] <= eps * (1 + 1e-4) + _ulp(FIELD), (eps, r["max_err"])


def test_cr_monotone_in_eps():
    crs = []
    for eps in (1e-4, 1e-3, 1e-2):
        spec = CompressionSpec(scheme="wavelet", wavelet="w3ai", eps=eps)
        crs.append(analyze_field(FIELD, spec)["cr"])
    assert crs[0] < crs[1] < crs[2]


def test_shuffle_improves_cr_same_psnr():
    a = analyze_field(FIELD, CompressionSpec(scheme="wavelet", shuffle="none"))
    b = analyze_field(FIELD, CompressionSpec(scheme="wavelet", shuffle="byte"))
    assert b["cr"] > a["cr"] * 0.98  # shuffling should not hurt
    assert abs(a["psnr"] - b["psnr"]) < 1e-9  # reversible: identical distortion


def test_byte_shuffle_roundtrip():
    rng = np.random.default_rng(0)
    buf = rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
    for itemsize in (2, 4, 8):
        s = shuf.byte_shuffle(buf, itemsize)
        assert shuf.byte_unshuffle(s, itemsize) == buf
        b = shuf.bit_shuffle(buf, itemsize)
        assert shuf.bit_unshuffle(b, itemsize) == buf


def _transposed(buf: bytes, itemsize: int) -> bytes:
    """The byte shuffle as it was first written: one strided transpose."""
    return np.frombuffer(buf, np.uint8).reshape(-1, itemsize).T.tobytes()


_DTYPE_OF_SIZE = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _as_kind(raw: bytes, kind: str, itemsize: int):
    if kind == "bytes":
        return raw
    if kind == "bytearray":
        return bytearray(raw)
    if kind == "uint8 array":
        return np.frombuffer(raw, np.uint8).copy()
    return np.frombuffer(raw, _DTYPE_OF_SIZE[itemsize]).copy()  # typed array


@pytest.mark.parametrize("nitems", [0, 1, 1001, 4096])
@pytest.mark.parametrize("kind", ["bytes", "bytearray", "uint8 array",
                                  "typed array"])
@pytest.mark.parametrize("itemsize", [1, 2, 4, 8])
def test_byte_shuffle_matches_the_transpose(itemsize, kind, nitems):
    """Byte planes written by column copies are the transpose's bytes, for
    any buffer type and size (an array reads as its bytes), and unshuffle
    back."""
    raw = np.random.default_rng(nitems).integers(
        0, 256, nitems * itemsize, dtype=np.uint8).tobytes()
    s = shuf.byte_shuffle(_as_kind(raw, kind, itemsize), itemsize)
    assert bytes(s) == _transposed(raw, itemsize)
    assert shuf.byte_unshuffle(s, itemsize) == raw


def test_byte_shuffle_writes_into_the_buffer_given():
    raw = np.arange(4 * 1001, dtype=np.uint8).tobytes()
    out = bytearray(3 + len(raw))
    view = memoryview(out)[3:]
    assert shuf.byte_shuffle(raw, 4, out=view) is view
    assert bytes(out) == bytes(3) + _transposed(raw, 4)


@pytest.mark.parametrize("kind", ["bytes", "bytearray", "uint8 array"])
@pytest.mark.parametrize("itemsize", [2, 4, 8])
def test_byte_shuffle_refuses_a_partial_item(itemsize, kind):
    raw = bytes(4 * itemsize + 1)
    with pytest.raises(ValueError, match="not divisible"):
        shuf.byte_shuffle(_as_kind(raw, kind, itemsize), itemsize)


def _serialized_by_concatenation(scheme, s1, lo, hi, spec) -> bytes:
    """The chunk layouts as ``tobytes`` concatenations, the way the schemes
    first wrote them."""
    def shuffled(b):
        if spec.shuffle == "none":
            return b
        return (_transposed(b, 4) if spec.shuffle == "byte"
                else shuf.bit_shuffle(b, 4))

    if scheme == "zfpx":
        emax = np.clip(s1["emax"][lo:hi], -127, 127).astype(np.int8)
        return emax.tobytes() + shuffled(
            s1["q"][lo:hi].astype(np.int32).tobytes())
    if scheme == "lorenzo":
        return shuffled(s1["res"][lo:hi].astype(np.int32).tobytes())
    mask, coeffs = s1["mask"][lo:hi], s1["coeffs"][lo:hi]
    values = np.concatenate([s1["coarse"][lo:hi].astype(np.float32).reshape(-1),
                             coeffs[mask].astype(np.float32)])
    counts = mask.reshape(mask.shape[0], -1).sum(-1).astype(np.uint32)
    return (counts.tobytes() + np.packbits(mask.reshape(-1)).tobytes()
            + shuffled(values.tobytes()))


@pytest.mark.parametrize("mode", ["byte", "none", "bit"])
@pytest.mark.parametrize("scheme", ["zfpx", "lorenzo", "wavelet"])
def test_payload_bytes_as_concatenated(scheme, mode):
    """Each chunk payload, written once into one buffer, holds the bytes the
    concatenating layout wrote."""
    from repro.core import blocks as blk
    from repro.core.schemes import get_scheme

    spec = CompressionSpec(scheme=scheme, eps=1e-3, block_size=16,
                           shuffle=mode)
    sch = get_scheme(scheme)
    s1 = sch.stage1(np.asarray(blk.blockify(FIELD, 16)), spec)
    for lo, hi in ((0, 3), (3, 4), (4, 9)):
        assert bytes(sch.serialize(s1, lo, hi, spec)) == \
            _serialized_by_concatenation(scheme, s1, lo, hi, spec)


def test_zero_low_bits():
    x = np.array([1.23456789, -9.87654e-3], np.float32)
    z = shuf.zero_low_bits_np(x, 8)
    assert np.all(z.view(np.uint32) & 0xFF == 0)
    assert np.max(np.abs(z - x) / np.abs(x)) < 2**-15


def test_zfp_lift_near_lossless():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.integers(-(2**27), 2**27, (64, 4, 4, 4)), jnp.int32)
    r = zfpx.inv_lift_cell(zfpx.fwd_lift_cell(q))
    assert int(jnp.max(jnp.abs(r - q))) <= 32  # bounded transform error


def test_zfpx_zero_block():
    blocks = jnp.zeros((2, 32, 32, 32), jnp.float32)
    emax, q = zfpx.encode(blocks, eps=1e-3)
    out = zfpx.decode(emax, q, eps=1e-3, n=32)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_szx_lorenzo_exact_int_roundtrip():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.integers(-(2**20), 2**20, (4, 16, 16, 16)), jnp.int32)
    r = szx.lorenzo_inv(szx.lorenzo_fwd(q))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(q))


def test_fpzipx_ordered_map_monotone():
    vals = np.array([-3e8, -1.0, -1e-20, -0.0, 0.0, 1e-20, 1.0, 3e8], np.float32)
    u = np.asarray(fpzipx.float_to_ordered(jnp.asarray(vals)))
    assert (np.diff(u.astype(np.int64)) >= 0).all()
    back = np.asarray(fpzipx.ordered_to_float(jnp.asarray(u)))
    np.testing.assert_array_equal(back[1:], vals[1:])  # -0.0 vs 0.0 aside


def test_topk_roundtrip():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((3, 16, 16, 16)), jnp.float32)
    from repro.core import wavelets as wv

    co = wv.forward3d(x, "w3ai")
    vals, idx, coarse = th.topk_details(co, k=128)
    cube = th.scatter_topk(vals, idx, coarse, 16)
    # kept coefficients match
    flat = np.asarray(co).reshape(3, -1)
    cube_flat = np.asarray(cube).reshape(3, -1)
    for b in range(3):
        np.testing.assert_allclose(
            cube_flat[b][np.asarray(idx)[b]], flat[b][np.asarray(idx)[b]], rtol=1e-6
        )


def test_container_roundtrip_and_block_reader(tmp_path):
    path = os.path.join(tmp_path, "p.cz")
    spec = CompressionSpec(scheme="wavelet", eps=1e-3, block_size=16, buffer_bytes=1 << 16)
    container.write_field(path, FIELD, spec)
    out = container.read_field(path)
    assert out.shape == FIELD.shape
    assert np.max(np.abs(out - FIELD)) < 1.0

    r = container.FieldReader(path, cache_chunks=2)
    blockA = r.read_block(0, 0, 0)
    assert blockA.shape == (16, 16, 16)
    np.testing.assert_allclose(blockA, out[:16, :16, :16], atol=1e-5)
    r.read_block(0, 0, 1)
    hits0 = r.cache_hits
    r.read_block(0, 0, 0)  # cached chunk
    assert r.cache_hits > hits0
    r.close()


def test_container_crc_detects_corruption(tmp_path):
    path = os.path.join(tmp_path, "p.cz")
    container.write_field(path, FIELD, CompressionSpec(scheme="raw"))
    with open(path, "r+b") as f:
        f.seek(-3, os.SEEK_END)
        f.write(b"\xff")
    with pytest.raises(IOError):
        container.read_field(path)


@settings(max_examples=15, deadline=None)
@given(
    scheme=st.sampled_from(["wavelet", "zfpx", "szx"]),
    eps=st.sampled_from([1e-4, 1e-3, 1e-2]),
    seed=st.integers(0, 100),
)
def test_property_bounded_error(scheme, eps, seed):
    f = smooth_field(n=32, seed=seed, noise=0.01)
    spec = CompressionSpec(scheme=scheme, eps=eps, block_size=16)
    r = analyze_field(f, spec)
    if scheme == "szx":
        assert r["max_err"] <= eps * (1 + 1e-4) + _ulp(f)
    else:
        assert r["max_err"] <= 300 * eps + 1e-5  # bounded amplification
    assert r["cr"] > 0.5
