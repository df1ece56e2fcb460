"""Cross-scheme conformance suite: one parametrized contract for **every**
registered scheme x device x dtype.

The contracts (per scheme, from its own declarations):

* round-trip error within ``Scheme.error_bound`` (bit-exact when None);
* CZ2 write -> re-read equals the in-memory decode exactly, with scheme,
  params and device recorded in the header;
* ``decode_spec`` is stable: identity at the current ``CODEC_FORMAT`` and
  idempotent for every historical format;
* device routing is never a decode requirement: a container written with
  ``device="jax"`` decodes on host (and vice versa) bit-exact for lossless
  layouts, within the declared bound for lossy ones;
* a dummy third-party ``@register_scheme`` plugin passes the same matrix.

Specs that reject a combination (e.g. fpzipx for non-float32) skip it —
rejection-at-validate is itself part of the contract.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import CODEC_FORMAT, CompressionSpec, Pipeline, SCHEMES, container
from repro.core.schemes import (
    DeviceFallbackWarning,
    Scheme,
    _device,
    get_scheme,
    register_scheme,
    shuffle_bytes,
    unregister_scheme,
    unshuffle_bytes,
)

DEVICES = ("host", pytest.param("jax", marks=pytest.mark.device))
DTYPES = ("float32", "float64", "float16")
BS = 8          # smallest side every scheme supports (2^k >= 8, % 4 == 0)
N = 24          # 27 blocks; with 8 KiB buffers -> 4 blocks/chunk, 7 chunks


def _field(dtype: str) -> np.ndarray:
    rng = np.random.default_rng(7)
    g = np.mgrid[0:N, 0:N, 0:N].astype(np.float32) / N
    f = 40.0 + 8.0 * np.sin(6 * g[0]) * np.cos(5 * g[1]) - 6.0 * g[2] ** 2
    f += rng.standard_normal((N, N, N)).astype(np.float32) * 0.05
    return f.astype(dtype)


#: the combos a scheme rejects *by contract* — only these may skip; any
#: other validation failure is a regression and fails the matrix outright
EXPECTED_REJECTS = {
    ("fpzipx", "float64"),   # lossless guarantee is float32-only
    ("fpzipx", "float16"),
}


def _spec(scheme: str, device: str = "host", dtype: str = "float32",
          **kw) -> CompressionSpec:
    spec = CompressionSpec(scheme=scheme, device=device, dtype=dtype,
                           eps=1e-3, block_size=BS, buffer_bytes=1 << 13, **kw)
    try:
        return spec.validate()
    except ValueError as e:
        if (scheme, dtype) in EXPECTED_REJECTS:
            pytest.skip(f"{scheme} rejects dtype={dtype} by contract: {e}")
        raise


def _tolerance(spec: CompressionSpec, field: np.ndarray) -> float:
    """Declared bound plus the unavoidable quanta: one ulp of the field's
    dtype at its magnitude (decode casts back to the tagged dtype)."""
    bound = get_scheme(spec.scheme).error_bound(spec)
    assert bound is not None
    absmax = float(np.abs(field).max())
    # lossy schemes compute in float32 and cast back to the tagged dtype:
    # allow one ulp at the field magnitude in whichever grid is coarser
    ulp = max(float(np.spacing(np.dtype(field.dtype).type(absmax))),
              float(np.spacing(np.float32(absmax))))
    return bound * (1 + 1e-4) + ulp


def _check_roundtrip(spec: CompressionSpec, field: np.ndarray) -> None:
    pipe = Pipeline(spec)
    comp = pipe.compress(field)
    assert len(comp.chunks) > 1, "conformance field must span several chunks"
    dec = pipe.decompress(comp)
    assert dec.shape == field.shape
    assert dec.dtype == field.dtype
    bound = get_scheme(spec.scheme).error_bound(spec)
    if bound is None:
        np.testing.assert_array_equal(dec, field)
    elif np.isfinite(bound):
        err = np.max(np.abs(dec.astype(np.float64) - field.astype(np.float64)))
        assert err <= _tolerance(spec, field), \
            f"{spec.scheme}: err {err:.3e} above declared bound {bound:.3e}"
    else:
        assert np.isfinite(dec).all()


def _ran_on(spec: CompressionSpec) -> str:
    """Where stage 1 actually runs for this spec: 'jax' only for a
    kernel-backed scheme with the Pallas toolchain importable — what the
    header must record as provenance."""
    sch = get_scheme(spec.scheme)
    capable = sch.device_capable and _device.kernel_ops() is not None
    return spec.device if capable else "host"


def _check_container(spec: CompressionSpec, field: np.ndarray, tmp_path) -> None:
    path = str(tmp_path / f"{spec.scheme}-{spec.device}-{spec.dtype}.cz")
    container.write_field(path, field, spec)
    pipe = Pipeline(spec)
    mem = pipe.decompress(pipe.compress(field))
    disk = container.read_field(path)
    np.testing.assert_array_equal(disk, mem)
    with container.FieldReader(path) as r:
        assert r.header["scheme"] == spec.scheme
        assert r.header["scheme_params"]["device"] == _ran_on(spec)
        assert r.header["format"] == CODEC_FORMAT
        np.testing.assert_array_equal(r.read_all(), mem)


def _check_decode_spec(spec: CompressionSpec) -> None:
    sch = get_scheme(spec.scheme)
    assert sch.decode_spec(spec, CODEC_FORMAT) == spec, \
        "decode_spec must be the identity at the current format"
    for fmt in range(1, CODEC_FORMAT + 1):
        ds = sch.decode_spec(spec, fmt)
        assert ds.scheme == spec.scheme
        assert sch.decode_spec(ds, fmt) == ds, \
            f"decode_spec must be idempotent (format {fmt})"


# ---------------------------------------------------------------------------
# The matrix: every registered scheme x device x dtype
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_roundtrip_within_declared_bound(scheme, device, dtype):
    _check_roundtrip(_spec(scheme, device, dtype), _field(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_cz2_write_reread_equality(scheme, device, dtype, tmp_path):
    _check_container(_spec(scheme, device, dtype), _field(dtype), tmp_path)


@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_decode_spec_stability(scheme, device):
    _check_decode_spec(_spec(scheme, device))


# ---------------------------------------------------------------------------
# Device routing is provenance, not a decode requirement
# ---------------------------------------------------------------------------

@pytest.mark.device
@pytest.mark.parametrize("write_dev,read_dev", [("jax", "host"), ("host", "jax")])
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_cross_device_decode(scheme, write_dev, read_dev, tmp_path):
    """A file written on one device decodes on the other: bit-exact for
    lossless layouts, within the declared bound for lossy ones."""
    field = _field("float32")
    spec = _spec(scheme, write_dev)
    path = str(tmp_path / f"{scheme}.cz")
    container.write_field(path, field, spec)
    dec = container.read_field(path, device=read_dev)
    bound = get_scheme(scheme).error_bound(spec)
    if bound is None:
        np.testing.assert_array_equal(dec, field)
    else:
        err = np.max(np.abs(dec.astype(np.float64) - field.astype(np.float64)))
        assert err <= _tolerance(spec, field)
    with container.FieldReader(path, device=read_dev) as r:
        assert r.spec.device == read_dev         # decode routing overridden
        # provenance records where stage 1 actually ran at write time
        assert r.header["scheme_params"]["device"] == _ran_on(spec)


def test_host_only_scheme_records_host_provenance(tmp_path):
    """szx/raw/fpzipx accept the device knob (a dataset-level spec may be
    shared across schemes) but have no kernel path — the header must record
    that stage 1 actually ran on host, not echo the knob."""
    spec = _spec("szx", "jax")
    path = str(tmp_path / "szx.cz")
    container.write_field(path, _field("float32"), spec)
    with container.FieldReader(path) as r:
        assert r.header["scheme_params"]["device"] == "host"
        assert r.spec.device == "jax"   # the requested knob stays in the spec


@pytest.mark.parametrize("scheme", ["wavelet", "zfpx", "lorenzo"])
def test_device_fallback_warns_and_matches_host(scheme, monkeypatch):
    """Without a Pallas toolchain, device='jax' degrades to the host path
    with a DeviceFallbackWarning — same bytes, nothing raised."""
    field = _field("float32")
    host = Pipeline(_spec(scheme, "host")).compress(field)
    monkeypatch.setattr(_device, "_OPS", None)   # simulate: kernels missing
    spec = _spec(scheme, "jax")
    with pytest.warns(DeviceFallbackWarning):
        jax_comp = Pipeline(spec).compress(field)
    assert jax_comp.chunks == host.chunks


# ---------------------------------------------------------------------------
# Unknown device= is rejected loudly, never silently run on the host path
# ---------------------------------------------------------------------------

def test_spec_rejects_unknown_device():
    with pytest.raises(ValueError, match="unknown device 'tpu'"):
        CompressionSpec(device="tpu").validate()


def test_shard_writer_rejects_unknown_device():
    from repro.store import ShardWriter

    with pytest.raises(ValueError, match="unknown device"):
        ShardWriter(CompressionSpec(scheme="raw", device="cuda"))
    # even a spec that dodged validation (e.g. rebuilt from a hand-edited
    # manifest) must fail in spec_for, not be warn-coerced onto the host path
    sw = ShardWriter(CompressionSpec(scheme="raw", block_size=BS))
    object.__setattr__(sw.spec, "device", "cuda")
    with pytest.raises(ValueError, match="unknown device 'cuda'"):
        sw.spec_for(_field("float64"))


@pytest.mark.parametrize("sub", [[], ["parallel"]])
def test_cli_rejects_unknown_device(sub, capsys):
    from repro.launch import compress

    with pytest.raises(SystemExit) as exc:
        compress.main(sub + ["--device", "tpu", "--n", str(N)])
    assert exc.value.code == 2
    assert "unknown device 'tpu'" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Third-party plugin through the same matrix
# ---------------------------------------------------------------------------

class OffsetScheme(Scheme):
    """Dummy third-party scheme: stores the negated field in the spec's
    tagged dtype (negation is IEEE-exact, so lossless for every dtype)."""

    name = "conformance-neg"

    def stage1(self, blocks_np, spec):
        return {"v": -np.asarray(blocks_np, spec.np_dtype)}

    def serialize(self, s1, lo, hi, spec):
        dt = spec.np_dtype
        return shuffle_bytes(s1["v"][lo:hi].tobytes(), spec.shuffle, dt.itemsize)

    def deserialize(self, payload, nblk, spec):
        dt = spec.np_dtype
        v = np.frombuffer(unshuffle_bytes(payload, spec.shuffle, dt.itemsize), dt)
        n = spec.block_size
        return -v.reshape(nblk, n, n, n)


@pytest.fixture()
def offset_scheme():
    register_scheme(OffsetScheme)
    yield OffsetScheme.name
    unregister_scheme(OffsetScheme.name)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("device", DEVICES)
def test_plugin_scheme_full_conformance(offset_scheme, device, dtype, tmp_path):
    field = _field(dtype)
    spec = _spec(offset_scheme, device, dtype)
    _check_roundtrip(spec, field)
    _check_container(spec, field, tmp_path)
    _check_decode_spec(spec)


def test_plugin_unregistered_cleanly(offset_scheme):
    assert offset_scheme in SCHEMES
    spec = dataclasses.replace(_spec(offset_scheme), extra={"knob": 1})
    assert get_scheme(offset_scheme).params(spec)["knob"] == 1


# ---------------------------------------------------------------------------
# Fields whose shape is not a multiple of the block size: padded edge blocks
# ---------------------------------------------------------------------------

#: (shape, block size): every axis partial; one axis shorter than a block
ODD_SHAPES = [((20, 36, 50), 8), ((5, 17, 33), 16)]
ODD_IDS = ["20x36x50-bs8", "5x17x33-bs16"]


def _odd_field(shape) -> np.ndarray:
    rng = np.random.default_rng(sum(shape))
    g = np.mgrid[tuple(slice(0, n) for n in shape)].astype(np.float32)
    f = 40.0 + 8.0 * np.sin(g[0] / 3) * np.cos(g[1] / 5) - 0.01 * g[2] ** 2
    return (f + 0.05 * rng.standard_normal(shape)).astype(np.float32)


def _odd_spec(scheme: str, device: str, bs: int) -> CompressionSpec:
    return dataclasses.replace(_spec(scheme, device), block_size=bs).validate()


def _reference(field: np.ndarray, spec: CompressionSpec):
    """The plain reference of padded encoding: each block cut from the field
    and padded with ``np.pad(mode="edge")`` on its own, the scheme's host
    stage 1 and byte layout over those blocks, and the decode cropped back
    block by block.  Returns ``(chunks, decoded field)``."""
    bs = spec.block_size
    host = Pipeline(dataclasses.replace(spec, device="host"))
    origins = [(x, y, z) for x in range(0, field.shape[0], bs)
               for y in range(0, field.shape[1], bs)
               for z in range(0, field.shape[2], bs)]
    blocks = []
    for o in origins:
        piece = field[tuple(slice(a, a + bs) for a in o)]
        blocks.append(np.pad(piece, [(0, bs - s) for s in piece.shape],
                             mode="edge"))
    comp = host.compress_blocks(np.stack(blocks))
    dec = host.decompress_blocks(comp)
    out = np.empty_like(field)
    for o, block in zip(origins, dec):
        box = tuple(slice(a, min(a + bs, n)) for a, n in zip(o, field.shape))
        out[box] = block[tuple(slice(0, s.stop - s.start) for s in box)]
    return comp.chunks, out


@pytest.mark.parametrize("shape,bs", ODD_SHAPES, ids=ODD_IDS)
@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_padded_field_matches_plain_reference(scheme, device, shape, bs):
    """Padded encoding against the plain reference: the payloads bit-exact
    for the integer-exact schemes (lorenzo, zfpx), on either device; every
    scheme's decode within its declared bound of the field."""
    field = _odd_field(shape)
    spec = _odd_spec(scheme, device, bs)
    comp = Pipeline(spec).compress(field)
    ref_chunks, ref_dec = _reference(field, spec)
    dec = Pipeline(spec).decompress(comp)
    assert dec.shape == field.shape
    assert comp.header["pad"] == "edge"
    assert comp.header["raw_bytes"] == field.nbytes
    if scheme in ("lorenzo", "zfpx") or device == "host":
        assert comp.chunks == ref_chunks
        np.testing.assert_array_equal(dec, ref_dec)
    bound = get_scheme(scheme).error_bound(spec)
    err = np.max(np.abs(dec.astype(np.float64) - field))
    if bound is None:
        assert err == 0
    else:
        assert err <= _tolerance(spec, field), (scheme, err, bound)


@pytest.mark.parametrize("shape,bs", ODD_SHAPES, ids=ODD_IDS)
@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_padded_cz2_write_reread_equality(scheme, device, shape, bs,
                                          tmp_path):
    """The conformance contract on a non-multiple shape: round trip within
    the declared bound, CZ2 re-read equal to the in-memory decode, and the
    padding recorded in the header."""
    field = _odd_field(shape)
    spec = _odd_spec(scheme, device, bs)
    _check_roundtrip(spec, field)
    _check_container(spec, field, tmp_path)
    path = str(tmp_path / f"{spec.scheme}-{spec.device}-{spec.dtype}.cz")
    d = container.describe(path)
    assert d["raw_bytes"] == field.nbytes
    assert d["nblocks"] == int(np.prod([-(-n // bs) for n in shape]))


@pytest.mark.device
@pytest.mark.parametrize("write_dev,read_dev", [("jax", "host"), ("host", "jax")])
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_padded_cross_device_decode(scheme, write_dev, read_dev, tmp_path):
    """A padded container written on one device decodes on the other,
    cropped to the field, within the declared bound."""
    (shape, bs), = ODD_SHAPES[:1]
    field = _odd_field(shape)
    spec = _odd_spec(scheme, write_dev, bs)
    path = str(tmp_path / f"{scheme}.cz")
    container.write_field(path, field, spec)
    dec = container.read_field(path, device=read_dev)
    assert dec.shape == field.shape
    bound = get_scheme(scheme).error_bound(spec)
    err = np.max(np.abs(dec.astype(np.float64) - field))
    assert err == 0 if bound is None else err <= _tolerance(spec, field)
    with container.FieldReader(path, device=read_dev) as r:
        np.testing.assert_array_equal(r.read_all(), dec)
