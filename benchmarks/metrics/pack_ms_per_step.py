"""The packer's own time per delivered batch (the ``pack`` stage, one span
per document fed, the waits for documents between them), over the window:
program counters ``stage.pack.busy_s`` and ``loader.batches_delivered``."""

from benchmarks.metrics.common import delta


def read(rec):
    busy = delta(rec, "stage.pack.busy_s")
    batches = delta(rec, "loader.batches_delivered")
    if busy is None or not batches:
        return None
    return 1e3 * busy / batches
