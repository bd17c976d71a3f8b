"""Share of its roofline that the on-device JPEG finish
(``jit_decode_coefficients``: dequantize, IDCT, upsample, colour) reaches:
the least time its bytes and operations allow on one chip over its device
time per call.  Memory-bound at 224x224 4:2:0."""

from benchmarks import roofline
from benchmarks.metrics.common import module, roofline_pct


def read(rec):
    m = module(rec, "decode")
    if m is None:
        return None
    s = rec["shapes"]
    ops, nbytes = roofline.jpeg_finish(s["rows"] // s["chips"], s["height"],
                                       s["width"], s["sampling"])
    return roofline_pct(rec, ops, nbytes, m[0])
