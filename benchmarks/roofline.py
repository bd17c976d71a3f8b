"""Operations and bytes of the program's kernels, computed from their
shapes, and a kernel's share of its roofline.

The least time a kernel could take on a chip is the larger of its
operations over the peak rate and its bytes over the memory bandwidth; its
roofline share is that least time over the time the trace measured.
"""

from __future__ import annotations

from typing import Sequence, Tuple


def jpeg_finish(rows: int, height: int, width: int,
                sampling: Sequence[Sequence[int]]) -> Tuple[float, float]:
    """``ops/jpeg.decode_coefficients`` over ``rows`` images: int16
    coefficient planes and uint16 quant tables in, uint8 RGB out.

    Operations: dequantize (1 per coefficient), the separable 8x8 inverse
    DCT (two 8x8x8 multiply-adds per block, 2 operations each), level shift
    and clamp (3 per sample), 2x triangle upsampling of each subsampled
    plane (4 per output sample per doubled axis), YCbCr->RGB (a 3x3
    multiply-add and 2 offsets per pixel), rounding and clipping (3 per
    output value)."""
    max_h = max(h for h, _ in sampling)
    max_v = max(v for _, v in sampling)
    coefs = ops = 0.0
    for h, v in sampling:
        bh = -(-height * v // max_v // 8)
        bw = -(-width * h // max_h // 8)
        blocks = bh * bw
        coefs += blocks * 64
        ops += blocks * (64 + 2 * 8 * 8 * 8 * 2 + 64 * 3)
        doubled = (max_h // h > 1) + (max_v // v > 1)
        ops += doubled * 4 * height * width
    pixels = height * width
    ops += pixels * (9 * 2 + 2) + pixels * 3 * 3
    nbytes = coefs * 2 + len(sampling) * 64 * 2 + pixels * 3
    return rows * ops, rows * nbytes


def normalize(rows: int, row_elems: int) -> Tuple[float, float]:
    """``ops/normalize`` (the Pallas kernel): uint8 in, bfloat16 out, one
    multiply-add per element against float32 scale and bias rows."""
    return 2.0 * rows * row_elems, rows * row_elems * 3.0 + 2 * row_elems * 4.0


def share_pct(ops: float, nbytes: float, seconds: float,
              peak: dict) -> Tuple[float, str]:
    """(percent of the roofline, which bound applies)."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
