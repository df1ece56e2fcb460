"""Operations and bytes each kernel's algorithm needs, from its shapes.

The counts describe the algorithm, not an implementation, so a rewrite of a
kernel is read against the same work:

* bytes are the least HBM traffic: the input read once and the outputs
  written once;
* operations are the arithmetic of the lifting steps, per element.

The least time of a call is the larger of bytes over the HBM peak and
operations over the compute peak (``peaks.json``).  The compute peak is the
chip's published bfloat16 rate; these kernels work in float32 and int32 on
the vector unit, whose rate is not published, so the compute roof is
optimistic and the bound it gives is a lower bound on time.
"""
from __future__ import annotations


def wavelet_levels(block: int) -> int:
    """Levels of the multi-level transform: coarse side kept >= 4."""
    levels = 0
    while block >= 8:
        block //= 2
        levels += 1
    return levels


def _lifting_ops(block: int) -> float:
    """w3ai lifting operations per element of a block, all levels: each 1D
    pass costs 8 per pair (average 2, 3-tap prediction 5, residual 1) on the
    forward side and 8 on the inverse side (prediction 5, residual 1,
    reconstruction 2), over three axes of a cube whose side halves each
    level."""
    per_level = 3 * 4.0
    return per_level * sum((1 / 8) ** lv for lv in range(wavelet_levels(block)))


def wavelet_forward(elements: int, block: int = 32) -> tuple[float, float]:
    """(operations, bytes): float32 block in, float32 coefficients out."""
    return elements * _lifting_ops(block), elements * 8.0


def wavelet_inverse(elements: int, block: int = 32) -> tuple[float, float]:
    """(operations, bytes): float32 coefficients in, float32 block out."""
    return elements * _lifting_ops(block), elements * 8.0


def zfpx_encode(elements: int, block: int = 32) -> tuple[float, float]:
    """(operations, bytes): float32 in, int32 ``q`` out, and one int32
    exponent per 4x4x4 cell.  Per element: the cell maximum (2), the
    scaling and rounding (3), the integer lifting along three axes (4 each)
    and the plane truncation (3)."""
    return elements * 20.0, elements * (4.0 + 4.0 + 4.0 / 64)


def lorenzo_encode(elements: int, block: int = 32) -> tuple[float, float]:
    """(operations, bytes): float32 in, int32 residuals out.  Per element:
    the quantization onto the 2*eps grid (3) and one integer difference
    along each axis (3)."""
    return elements * 6.0, elements * 8.0


KERNELS = {"wavelet_forward": wavelet_forward,
           "wavelet_inverse": wavelet_inverse,
           "zfpx_encode": zfpx_encode,
           "lorenzo_encode": lorenzo_encode}


def least_time(kernel: str, elements: int, peaks: dict,
               block: int = 32) -> tuple[float, str]:
    """(seconds, "memory" | "compute"): the least time the chip could take
    for ``elements`` elements of ``kernel``, and which roof binds."""
    ops, nbytes = KERNELS[kernel](elements, block)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


def share(obs, kernel: str) -> float | None:
    """Percent of the roofline that ``kernel`` reached in a traced window:
    its least time for the elements it processed over the device time of
    the jitted program that wraps it.  ``None`` where the window ran no
    such work or the trace holds no such program."""
    elements = obs.counters.get("kernel_elements", {}).get(kernel, 0)
    if obs.device is None or obs.peaks is None or not elements:
        return None
    seconds = obs.device.program_seconds(kernel)
    if seconds <= 0:
        return None
    least, _ = least_time(kernel, elements, obs.peaks,
                          block=int(obs.config["block"]))
    return 100.0 * least / seconds
