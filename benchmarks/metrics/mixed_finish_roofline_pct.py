"""Share of its roofline that the on-chip finish of a 'device-mixed' batch
reaches: the finish programs' device time per batch (``jit_finish_canvas``,
one call per canvas group, over the consumer steps in the trace) against
``roofline.jpeg_finish`` over the images' own mean geometry, not the
canvases: the yardstick reads the same work whatever implements it, and
the padding the canvases add counts against the share.  Memory-bound."""

from benchmarks import roofline
from benchmarks.metrics.common import module, roofline_pct


def read(rec):
    finish, step = module(rec, "finish"), module(rec, "step")
    s = rec["shapes"]
    if finish is None or step is None or not s.get("mean_height"):
        return None
    seconds = finish[0] * finish[1] / step[1]
    ops, nbytes = roofline.jpeg_finish(
        s["rows"] // s["chips"], round(s["mean_height"]),
        round(s["mean_width"]), s["sampling"])
    return roofline_pct(rec, ops, nbytes, seconds)
