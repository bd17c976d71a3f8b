"""Host busy time of the workers' decode stage (Parquet read and JPEG
entropy decode) per row decoded, over the window: program counters
``stage.decode.busy_s`` and ``worker.rows_decoded``."""

from benchmarks.metrics.common import delta


def read(rec):
    busy, rows = delta(rec, "stage.decode.busy_s"), delta(rec, "worker.rows_decoded")
    if not busy or not rows:
        return None
    return 1e3 * busy / rows
