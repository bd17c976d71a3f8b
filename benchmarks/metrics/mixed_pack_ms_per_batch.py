"""Host time per delivered batch the loader's assembly thread spent
padding coefficient planes into canvases (the ``mixed-pack`` stage), over
the window: program counters ``stage.mixed-pack.busy_s`` and
``loader.batches_delivered``."""

from benchmarks.metrics.common import delta


def read(rec):
    busy = delta(rec, "stage.mixed-pack.busy_s")
    batches = delta(rec, "loader.batches_delivered")
    if busy is None or not batches:
        return None
    return 1e3 * busy / batches
