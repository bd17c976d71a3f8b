"""Host busy time of the workers' Parquet read per row decoded, over the
window: program counters ``stage.read.busy_s`` (the ``read`` stage, the
io-window prefetch and ``read_row_group``, nested in ``decode``) and
``worker.rows_decoded``."""

from benchmarks.metrics.common import delta


def read(rec):
    busy, rows = delta(rec, "stage.read.busy_s"), delta(rec, "worker.rows_decoded")
    if busy is None or not rows:
        return None
    return 1e3 * busy / rows
