"""Compile-only checks of the main-path Pallas kernels for one TPU v5e chip.

Each ``repro.kernels.ops`` wrapper is lowered with ``interpret=False`` and
compiled by the TPU compiler for a *described* v5e chip — no chip needed.
That catches what interpret mode cannot: primitives Mosaic has no lowering
for, block shapes that break the (8, 128) tiling rule, and kernels that
overrun scoped VMEM.  Shapes are the real ones: 4096 blocks of 32^3 (a
512^3 field) and 512 blocks (a 256^3 field).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every pytest worker imports
this file.  Where no topology can be described, the fixture skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 32
NC = (N // 4) ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _args(kernel: str, b: int, sharding):
    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    blocks = arr((b, N, N, N), jnp.float32)
    return {
        "wavelet_forward": ((blocks,), {"kind": "w3ai"}),
        "wavelet_inverse": ((blocks,), {"kind": "w3ai"}),
        "zfpx_encode": ((blocks,), {"eps": 1e-3}),
        "zfpx_decode": ((arr((b, NC), jnp.int32), arr((b, NC, 64), jnp.int32)),
                        {"eps": 1e-3, "n": N}),
        "lorenzo_encode": ((blocks,), {"eps": 1e-3}),
        "lorenzo_decode": ((arr((b, N, N, N), jnp.int32),), {"eps": 1e-3}),
    }[kernel]


@pytest.mark.parametrize("b", [4096, 512])
@pytest.mark.parametrize("kernel", ops.__all__)
def test_kernel_compiles_for_v5e(kernel, b, one_chip):
    args, kw = _args(kernel, b, one_chip)
    jitted = getattr(ops, kernel).__wrapped__   # the jit under the metrics
    compiled = jitted.lower(*args, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{kernel}: no Pallas kernel in the compiled program"
    mem = compiled.memory_analysis()
    raw = b * N ** 3 * 4
    # arguments + outputs + temporaries of one call stay within a fixed
    # multiple of the raw block batch (the wavelet kernel's lane-padded
    # 32-wide blocks take the most: 10x), so a 512^3 batch fits one chip
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total <= 12 * raw, f"{kernel}: {total} bytes for {raw} raw"
