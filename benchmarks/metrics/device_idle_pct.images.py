"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""

from benchmarks.metrics.common import device_idle_pct as read  # noqa: F401
