"""Device time of one run of the consumer's jitted train step, from the
trace: the ceiling every ingest gain runs into."""

from benchmarks.metrics.common import step_device_ms as read  # noqa: F401
