"""Share of the canvas pixels the 'device-mixed' finish computes that lie
inside an image, over the window: program counters
``loader.mixed_pixels_valid`` (the images' own extents) and
``loader.mixed_pixels_canvas`` (the canvases they were packed into)."""

from benchmarks.metrics.common import delta


def read(rec):
    valid = delta(rec, "loader.mixed_pixels_valid")
    canvas = delta(rec, "loader.mixed_pixels_canvas")
    if valid is None or not canvas:
        return None
    return 100.0 * valid / canvas
