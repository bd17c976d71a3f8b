"""Mean time per delivered batch the loader's transfer thread waited for
the batch's arrays to be ready (the ``commit`` stage, nested in
``device-transfer``; the on-chip decode finish included), over the window:
program counters ``stage.commit.busy_s`` and ``loader.batches_delivered``.
Reads 0 when the loader runs without per-batch commits."""

from benchmarks.metrics.common import delta


def read(rec):
    busy = delta(rec, "stage.commit.busy_s")
    batches = delta(rec, "loader.batches_delivered")
    if busy is None or not batches:
        return None
    return 1e3 * busy / batches
