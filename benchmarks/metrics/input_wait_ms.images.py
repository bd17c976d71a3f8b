"""Mean time per step the loop spent in ``next(loader)``, on the host clock
(the benchmark's ``input_wait`` span): what assembly and commit did not
hide behind the step in flight."""

from benchmarks.metrics.common import mean_input_wait_ms as read  # noqa: F401
