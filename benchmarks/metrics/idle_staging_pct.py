"""Share of the traced window, mean over chips, in which the chip is idle
while the loader's transfer thread stages a batch: inside its
``device-transfer`` span but not in the ``commit`` child (the program's
``petastorm/*`` spans on the device's clock, ``benchmarks/program_spans.py``)."""

import os

from benchmarks import program_spans

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(rec):
    reduced = program_spans.for_record(rec, BENCH_DIR)
    if reduced is None or reduced["idle_staging_s"] is None:
        return None
    return 100.0 * reduced["idle_staging_s"] / reduced["window_s"]
