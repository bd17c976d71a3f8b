#!/usr/bin/env python
"""Thread-pool wedge soak: hammer the oversubscribed reader until it wedges.

Post-mortem tool for the round-5 hang (a full-suite run froze
with one worker stuck inside a timed queue get while ``join()`` waited on it
forever).  Runs the oversubscribed stress-test loop continuously with a
PROGRESS-based watchdog: wall-clock slowness from competing load never
fires it; only a genuine absence of batches for ``--wedge-after`` seconds
does.  On a wedge it writes every thread's Python stack AND each OS
thread's in-flight syscall + kernel wait channel (/proc/self/task) to the
dump file — enough to distinguish "stuck in a C-level timed lock wait"
from "waiting for the GIL" — then exits 3.

Usage:  python tools/stress_soak.py [--seconds 14400] [--dump /tmp/soak_dump.txt]
"""
import argparse
import collections
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

from petastorm_tpu.codecs import NdarrayCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from tools.soak_common import start_progress_watchdog, validated_dataset

ROWS = 192  # 48 rowgroups x 4 rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=14400)
    ap.add_argument("--wedge-after", type=float, default=150,
                    help="seconds without a consumed batch that count as a wedge")
    ap.add_argument("--dump", default="/tmp/soak_dump.txt")
    ap.add_argument("--dataset", default="/tmp/stress_soak_ds")
    args = ap.parse_args()

    def build(url):
        schema = Schema("Stress", [
            Field("id", np.int64),
            Field("payload", np.float32, (64,), NdarrayCodec()),
        ])
        write_dataset(url, schema,
                      [{"id": i, "payload": np.full(64, i, np.float32)}
                       for i in range(ROWS)],
                      row_group_size_rows=4)

    validated_dataset(args.dataset, ROWS, build)
    progress = [0]
    start_progress_watchdog(progress, args.wedge_after, args.dump,
                            label="stress_soak")

    t_start = time.time()
    i = 0
    while time.time() - t_start < args.seconds:
        i += 1
        for workers in (8, 16):
            for epochs in (1, 3):
                with make_batch_reader(args.dataset, reader_pool_type="thread",
                                       workers_count=workers, shuffle_seed=2,
                                       num_epochs=epochs) as r:
                    seen = []
                    for b in r.iter_batches():
                        seen.extend(int(v) for v in b.columns["id"])
                        progress[0] += 1
                    state = r.state_dict()
                counts = collections.Counter(seen)
                assert sorted(counts) == list(range(ROWS)), f"iter {i} loss/dup"
                assert set(counts.values()) == {epochs}
                assert state["position"] == epochs * 48
        progress[0] += 1
        if i % 25 == 0:
            print(f"iter {i} ok t={time.time() - t_start:.0f}s", flush=True)
    print(f"done: {i} iterations, no wedge in {args.seconds:.0f}s", flush=True)


if __name__ == "__main__":
    main()
