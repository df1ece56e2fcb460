"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode,
plus hypothesis parity sweeps over random shapes/levels/eps.  The property
tests skip cleanly on a bare interpreter (no hypothesis); any environment
installed via ``pip install -e ".[test]"`` — both CI jobs included — has
hypothesis and runs them for real."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 runs without hypothesis
    from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

pytestmark = pytest.mark.device


def blocks(b, n, seed=0, scale=50.0):
    rng = np.random.default_rng(seed)
    # smooth-ish blocks: random low-order polynomial + small noise
    g = np.mgrid[0:n, 0:n, 0:n].astype(np.float32) / n
    out = np.empty((b, n, n, n), np.float32)
    for i in range(b):
        c = rng.standard_normal(9).astype(np.float32)
        out[i] = scale * (
            c[0] + c[1] * g[0] + c[2] * g[1] + c[3] * g[2]
            + c[4] * g[0] * g[1] + c[5] * g[1] * g[2]
            + c[6] * g[0] ** 2 + c[7] * g[1] ** 2 + c[8] * g[2] ** 2
        ) + rng.standard_normal((n, n, n)).astype(np.float32) * 0.01 * scale
    return jnp.asarray(out)


@pytest.mark.parametrize("kind", ["w4i", "w4l", "w3ai"])
@pytest.mark.parametrize("b,n", [(1, 8), (4, 16), (3, 32), (8, 32)])
def test_wavelet_kernel_matches_ref(kind, b, n):
    x = blocks(b, n, seed=n + b)
    got = ops.wavelet_forward(x, kind=kind, interpret=True)
    want = ref.wavelet3d_forward_ref(x, kind=kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=2e-3)
    back = ops.wavelet_inverse(got, kind=kind, interpret=True)
    scale = float(np.max(np.abs(np.asarray(x))))
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=1e-5, atol=1e-4 * scale)


@pytest.mark.parametrize("eps", [1e-4, 1e-2])
@pytest.mark.parametrize("b,n", [(2, 8), (4, 16), (5, 32)])
def test_zfpx_kernel_matches_ref(eps, b, n):
    x = blocks(b, n, seed=b * n)
    e_got, q_got = ops.zfpx_encode(x, eps=eps, interpret=True)
    e_want, q_want = ref.zfpx_encode_ref(x, eps=eps)
    np.testing.assert_array_equal(np.asarray(e_got), np.asarray(e_want))
    np.testing.assert_array_equal(np.asarray(q_got), np.asarray(q_want))
    d_got = ops.zfpx_decode(e_got, q_got, eps=eps, n=n, interpret=True)
    d_want = ref.zfpx_decode_ref(e_want, q_want, eps=eps, n=n)
    np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_want), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("eps", [1e-3, 1e-1])
@pytest.mark.parametrize("b,n", [(2, 8), (4, 16), (3, 32), (16, 16)])
def test_lorenzo_kernel_matches_ref(eps, b, n):
    x = blocks(b, n, seed=7 * b + n)
    r_got = ops.lorenzo_encode(x, eps=eps, interpret=True)
    r_want = ref.lorenzo_encode_ref(x, eps=eps)
    np.testing.assert_array_equal(np.asarray(r_got), np.asarray(r_want))
    d_got = ops.lorenzo_decode(r_got, eps=eps, interpret=True)
    d_want = ref.lorenzo_decode_ref(r_want, eps=eps)
    np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_want), rtol=1e-6)
    assert float(jnp.max(jnp.abs(d_got - x))) <= eps * (1 + 1e-4) + 1e-5


def test_kernels_handle_non_divisible_batch():
    x = blocks(5, 16, seed=11)  # 5 % 4 != 0 -> tile fallback path
    got = ops.wavelet_forward(x, kind="w3ai", interpret=True)
    want = ref.wavelet3d_forward_ref(x, kind="w3ai")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=2e-3)


def test_wavelet_kernel_dtype_promotion():
    x = blocks(2, 16).astype(jnp.float64) if False else blocks(2, 16)
    got = ops.wavelet_forward(x.astype(jnp.bfloat16), kind="w3ai", interpret=True)
    assert got.dtype == jnp.float32  # kernels compute in f32
    assert np.isfinite(np.asarray(got)).all()


# ---------------------------------------------------------------------------
# Odd / non-multiple-of-block grid sizes (the tile-fallback and non-2^k paths)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,n", [(1, 6), (5, 10), (7, 12), (3, 20)])
def test_lorenzo_kernel_odd_sizes(b, n):
    """Lorenzo works at any block side, including odd and non-2^k."""
    x = blocks(b, n, seed=b * 31 + n)
    r_got = ops.lorenzo_encode(x, eps=1e-3, interpret=True)
    np.testing.assert_array_equal(np.asarray(r_got),
                                  np.asarray(ref.lorenzo_encode_ref(x, eps=1e-3)))
    d = ops.lorenzo_decode(r_got, eps=1e-3, interpret=True)
    assert float(jnp.max(jnp.abs(d - x))) <= 1e-3 * (1 + 1e-4) + 1e-5


@pytest.mark.parametrize("b,n", [(3, 12), (7, 20)])
def test_zfpx_kernel_non_pow2_sizes(b, n):
    """zfpx needs n % 4 == 0 only — non-power-of-two sides are exact too."""
    x = blocks(b, n, seed=b + 3 * n)
    e_got, q_got = ops.zfpx_encode(x, eps=1e-3, interpret=True)
    e_want, q_want = ref.zfpx_encode_ref(x, eps=1e-3)
    np.testing.assert_array_equal(np.asarray(e_got), np.asarray(e_want))
    np.testing.assert_array_equal(np.asarray(q_got), np.asarray(q_want))
    d_got = ops.zfpx_decode(e_got, q_got, eps=1e-3, n=n, interpret=True)
    d_want = ref.zfpx_decode_ref(e_want, q_want, eps=1e-3, n=n)
    np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_want),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("levels", [1, 2])
@pytest.mark.parametrize("b", [1, 7])
def test_wavelet_kernel_explicit_levels(b, levels):
    x = blocks(b, 16, seed=b + levels)
    got = ops.wavelet_forward(x, kind="w3ai", levels=levels, interpret=True)
    want = ref.wavelet3d_forward_ref(x, kind="w3ai", levels=levels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-3)
    back = ops.wavelet_inverse(got, kind="w3ai", levels=levels, interpret=True)
    scale = float(np.max(np.abs(np.asarray(x))))
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               rtol=1e-5, atol=1e-4 * scale)


# ---------------------------------------------------------------------------
# Hypothesis parity sweeps: kernels vs references on random shapes/levels/eps
# ---------------------------------------------------------------------------

def _rand_blocks(b, n, seed, scale):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.uniform(-scale, scale, (b, n, n, n)).astype(np.float32))


@settings(max_examples=25, deadline=None)
@given(b=st.integers(1, 8), n=st.sampled_from([4, 6, 8, 10, 12, 16]),
       eps=st.sampled_from([1e-4, 1e-3, 1e-1]), seed=st.integers(0, 2**16),
       scale=st.floats(1e-2, 1e3))
def test_lorenzo_parity_property(b, n, eps, seed, scale):
    """Kernel and host reference are *integer-exact* on any shape, and the
    reconstruction respects the eps bound."""
    x = _rand_blocks(b, n, seed, scale)
    r_got = ops.lorenzo_encode(x, eps=eps, interpret=True)
    r_want = ref.lorenzo_encode_ref(x, eps=eps)
    np.testing.assert_array_equal(np.asarray(r_got), np.asarray(r_want))
    d = ops.lorenzo_decode(r_got, eps=eps, interpret=True)
    ulp = float(np.spacing(np.float32(scale)))
    assert float(jnp.max(jnp.abs(d - x))) <= eps * (1 + 1e-4) + ulp


@settings(max_examples=20, deadline=None)
@given(b=st.integers(1, 6), n=st.sampled_from([4, 8, 12, 16]),
       eps=st.sampled_from([1e-4, 1e-2]), seed=st.integers(0, 2**16))
def test_zfpx_parity_property(b, n, eps, seed):
    x = _rand_blocks(b, n, seed, 50.0)
    e_got, q_got = ops.zfpx_encode(x, eps=eps, interpret=True)
    e_want, q_want = ref.zfpx_encode_ref(x, eps=eps)
    np.testing.assert_array_equal(np.asarray(e_got), np.asarray(e_want))
    np.testing.assert_array_equal(np.asarray(q_got), np.asarray(q_want))
    d_got = ops.zfpx_decode(e_got, q_got, eps=eps, n=n, interpret=True)
    d_want = ref.zfpx_decode_ref(e_want, q_want, eps=eps, n=n)
    np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_want),
                               rtol=1e-6, atol=1e-7)


@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 5), n=st.sampled_from([8, 16, 32]),
       kind=st.sampled_from(["w4i", "w4l", "w3ai"]),
       levels=st.sampled_from([None, 1, 2]), seed=st.integers(0, 2**16))
def test_wavelet_parity_property(b, n, kind, levels, seed):
    x = _rand_blocks(b, n, seed, 50.0)
    got = ops.wavelet_forward(x, kind=kind, levels=levels, interpret=True)
    want = ref.wavelet3d_forward_ref(x, kind=kind, levels=levels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-3)
    back = ops.wavelet_inverse(got, kind=kind, levels=levels, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               rtol=1e-5, atol=1e-4 * 50.0)


def test_kernel_metrics_split_compile_from_execute():
    """The device-tier counters distinguish the first call per signature
    (jit compile) from steady-state execution: compiles_total advances once
    per new signature, calls_total per call."""
    from repro import obs

    dev = __import__("jax").default_backend()
    kernel = "lorenzo_encode"
    lbl = {"kernel": kernel, "device": dev}
    compiles = obs.REGISTRY.get("cz_kernel_compiles_total")
    calls = obs.REGISTRY.get("cz_kernel_calls_total")
    assert "cz_kernel_seconds" not in {m.name for m in obs.REGISTRY}

    x = blocks(9, 8, seed=991)  # fresh shape: unseen by earlier tests
    c0, n0 = compiles.value(**lbl), calls.value(**lbl)
    ops.lorenzo_encode(x, eps=2e-3, interpret=True)
    assert compiles.value(**lbl) == c0 + 1
    assert calls.value(**lbl) == n0 + 1
    for _ in range(2):  # same signature: execute, no new compile
        ops.lorenzo_encode(x, eps=2e-3, interpret=True)
    assert compiles.value(**lbl) == c0 + 1
    assert calls.value(**lbl) == n0 + 3
    # a new eps is a new operand value, not a new program: no compile
    ops.lorenzo_encode(x, eps=3e-3, interpret=True)
    assert compiles.value(**lbl) == c0 + 1
    assert calls.value(**lbl) == n0 + 4


def _eps_calls():
    """Each kernel wrapper and host reference that takes ``eps``, as a call
    on fresh blocks of an eps, and the jitted function it runs."""
    from repro.core import szx, zfpx

    x = blocks(6, 8, seed=517)
    emax, q = zfpx.encode(x, eps=1e-3)
    res = szx.encode(x, eps=1e-3)
    k = dict(interpret=True)
    return {
        "zfpx_encode": (lambda e: ops.zfpx_encode(x, eps=e, **k),
                        ops.zfpx_encode.__wrapped__),
        "zfpx_decode": (lambda e: ops.zfpx_decode(emax, q, eps=e, n=8, **k),
                        ops.zfpx_decode.__wrapped__),
        "lorenzo_encode": (lambda e: ops.lorenzo_encode(x, eps=e, **k),
                           ops.lorenzo_encode.__wrapped__),
        "lorenzo_decode": (lambda e: ops.lorenzo_decode(res, eps=e, **k),
                           ops.lorenzo_decode.__wrapped__),
        "zfpx_host_encode": (lambda e: zfpx.encode(x, eps=e), zfpx._encode),
        "zfpx_host_decode": (lambda e: zfpx.decode(emax, q, eps=e, n=8),
                             zfpx._decode),
        "szx_host_encode": (lambda e: szx.encode(x, eps=e), szx._encode),
        "szx_host_decode": (lambda e: szx.decode(res, eps=e), szx._decode),
    }


@pytest.mark.parametrize("name", [
    "zfpx_encode", "zfpx_decode", "lorenzo_encode", "lorenzo_decode",
    "zfpx_host_encode", "zfpx_host_decode", "szx_host_encode",
    "szx_host_decode"])
def test_one_program_serves_every_eps(name):
    """``eps`` reaches the jit as an operand: fields of other eps (as a
    value-range-relative bound gives every member) reuse the program
    compiled for the first, and still get their own results."""
    call, jitted = _eps_calls()[name]
    call(1e-3)
    size = jitted._cache_size()
    outs = [np.asarray(jax.tree_util.tree_leaves(call(eps))[-1])
            for eps in (1e-3, 2.7e-3, 4.1e-2, 7.3e-5)]
    assert jitted._cache_size() == size
    # zfpx decodes from q alone; every other result follows its eps
    assert np.array_equal(outs[0], outs[2]) == ("zfpx" in name
                                                and "decode" in name)


def test_kernel_wrapper_returns_unforced_while_tracing(monkeypatch):
    """With the process tracer on, a wrapper call neither blocks nor
    records a span of its own: the traced run dispatches as the untraced
    one does, and kernel time comes from the profiler's device trace."""
    import jax

    from repro import obs

    def forbidden(x):
        raise AssertionError("kernel wrapper forced its result")

    x = blocks(3, 8, seed=771)
    monkeypatch.setattr(jax, "block_until_ready", forbidden)
    obs.trace.reset()
    obs.trace.enable()
    try:
        out = ops.lorenzo_encode(x, eps=5e-3, interpret=True)
        out2 = ops.lorenzo_encode(x, eps=5e-3, interpret=True)
    finally:
        obs.trace.disable()
    events = obs.TRACER.events()
    obs.trace.reset()
    monkeypatch.undo()
    assert isinstance(out, jax.Array) and isinstance(out2, jax.Array)
    assert not [e for e in events if e["name"].startswith("kernel.")]
    ref = ops.lorenzo_encode(x, eps=5e-3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
