"""Guards that keep the chip visible: no silent host fallback, no silent
interpret mode, no child process reaching for the chip, a compile cache
placed from outside, and ``chip_smoke.py``'s phases rehearsed on the CPU.

Where a guard depends on the backend, the test steers it by patching
``jax.default_backend`` — the program has no option for it.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro import obs
from repro.cluster import ParallelCompressor
from repro.cluster._env import worker_env
from repro.cluster.engine import check_rank_device
from repro.core import CompressionSpec
from repro.core.schemes import DeviceFallbackWarning, _device
from repro.kernels import ops
from repro.launch import compress, jax_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interp(None)
    else:
        assert ops._interp(None) is want
    assert ops._interp(True) is True and ops._interp(False) is False


def test_broken_kernel_module_raises(monkeypatch):
    """Only a JAX without Pallas may fall back; a kernel module that fails
    to import for another reason surfaces."""
    import repro.kernels

    monkeypatch.setattr(_device, "_OPS", _device._UNSET)
    monkeypatch.delattr(repro.kernels, "ops")
    monkeypatch.setitem(sys.modules, "repro.kernels.ops", None)
    with pytest.raises(ImportError, match="repro.kernels.ops"):
        _device.kernel_ops()
    assert _device._OPS is _device._UNSET


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_compile_cache_defaults_to_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = jax_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert not jax.config.jax_enable_compilation_cache  # tests keep it off


def test_rank_workers_are_pinned_to_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with worker_env():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert "XLA_FLAGS" not in os.environ or \
            "multi_thread_eigen" not in os.environ["XLA_FLAGS"]
    assert os.environ["JAX_PLATFORMS"] == "tpu"


def test_device_ranks_refused_on_accelerator(monkeypatch, tmp_path):
    """A device='jax' spec on a TPU parent would run CPU ranks whose bytes
    differ from the serial writer's: refused before any worker starts."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    field = np.zeros((32, 32, 32), np.float32)
    spec = CompressionSpec(scheme="lorenzo", device="jax", block_size=16,
                           buffer_bytes=1 << 14)
    with ParallelCompressor(2) as pc:
        with pytest.raises(ValueError, match="one process per chip"):
            pc.compress(str(tmp_path / "x.cz"), field, spec)
        assert pc._pool is None
    # host specs, and device specs on one rank, stay allowed
    check_rank_device(CompressionSpec(scheme="lorenzo"), 2)
    check_rank_device(spec, 1)


def test_cli_device_ranks_is_usage_error(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit) as e:
        compress.main(["parallel", "--device", "jax", "--ranks", "2",
                       "--n", "32", "--out", str(tmp_path)])
    assert e.value.code == 2
    assert "one process per chip" in capsys.readouterr().err
    assert os.listdir(tmp_path) == []


def test_smoke_phases_on_cpu(tmp_path):
    """The smoke's three phases at 64^3 on the CPU (interpret-mode
    kernels): every member within its bound, zfpx/lorenzo bit-exact, no
    fallback."""
    smoke = _load_smoke()
    fallbacks = obs.REGISTRY.get("cz_kernel_fallbacks_total")
    before = fallbacks.value()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeviceFallbackWarning)
        report, fields, roots = smoke.insitu(str(tmp_path), n=64, steps=2)
        ex = smoke.exsitu(str(tmp_path), n=64)
        reg = smoke.regions(roots, fields, n=64)
    assert fallbacks.value() == before
    assert [(m["scheme"], m["quantity"]) for m in report["members"]] == [
        (s, q) for s in smoke.KERNEL_SCHEMES for q in ("p", "rho", "E")]
    assert all(m["bit_exact"] for m in report["members"]
               if m["scheme"] in smoke.EXACT_SCHEMES)
    assert [m["quantity"] for m in ex["members"]] == list(smoke.EXSITU_QOIS)
    assert len(reg["queries"]) == 3 * 3 * len(smoke.boxes(64))


def _run_smoke(script: str, cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_fails_without_tpu():
    r = _run_smoke(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_smoke_fails_outside_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run_smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
