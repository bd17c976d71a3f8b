#!/usr/bin/env python
"""Randomized concurrency fuzz over reader configurations.

Complements tools/stress_soak.py (fixed oversubscribed configs): every
iteration draws a random configuration — pool flavor, worker count,
epochs, shuffle seed, and a consumption pattern (plain read / mid-stream
quiesce+checkpoint+resume / two-shard union, static or epoch shard mode)
— and asserts the exact-multiset invariant: every row id appears exactly
``num_epochs`` times, across incarnations and shards.  Any loss,
duplication, wedge (progress watchdog), or crash is a finding; the seed
printed with the failure reproduces the configuration.

Reference analog: the pool matrix + end-to-end shard tests
(petastorm/tests/test_end_to_end.py:395-462, workers_pool/tests) — run as
an open-ended randomized soak instead of a fixed matrix.

Usage: python tools/concurrency_fuzz.py [--seconds 3600] [--seed-base 0]
Exit 3 = wedge; assertion failure = invariant violation (seed in message).
"""
import argparse
import collections
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

from tools.soak_common import start_progress_watchdog, validated_dataset

ROWS = 96  # 24 rowgroups x 4 rows


def build_datasets(root):
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.schema import Field, Schema

    def build(url):
        schema = Schema("Fuzz", [
            Field("id", np.int64),
            Field("payload", np.float32, (32,), NdarrayCodec()),
        ])
        write_dataset(url, schema,
                      [{"id": i, "payload": np.full(32, i, np.float32)}
                       for i in range(ROWS)],
                      row_group_size_rows=4)

    return [validated_dataset(os.path.join(root, "plain"), ROWS, build)]


def run_plain(make_batch_reader, url, cfg):
    with make_batch_reader(url, **cfg) as r:
        return [int(v) for b in r.iter_batches() for v in b.columns["id"]]


def run_resume(make_batch_reader, url, cfg, rnd):
    """Consume a random prefix, quiesce + drain, checkpoint, resume."""
    seen = []
    k = rnd.randint(0, 10)
    with make_batch_reader(url, **cfg) as r:
        it = r.iter_batches()
        for _ in range(k):
            try:
                b = next(it)
            except StopIteration:
                break
            seen.extend(int(v) for v in b.columns["id"])
        r.quiesce()
        for b in it:  # drain the already-ventilated in-flight window
            seen.extend(int(v) for v in b.columns["id"])
        state = r.state_dict()
        assert state["ordinal_exact"], f"cursor not exact after drain: {state}"
    with make_batch_reader(url, resume_from=state, **cfg) as r:
        seen.extend(int(v) for b in r.iter_batches()
                    for v in b.columns["id"])
    return seen


def run_loader(make_batch_reader, url, cfg, rnd):
    """Random drain point + resume through JaxDataLoader (single CPU device),
    with random batch size, stack_batches, and HBM shuffle settings.  Honors
    the `_valid_rows` contract: scalar for plain partial batches, (K,) per
    step for stacked units."""
    from petastorm_tpu.jax import JaxDataLoader

    batch = rnd.choice([4, 8, 16])
    stack = rnd.choice([1, 1, 2, 4])
    loader_kw = dict(batch_size=batch, drop_last=False, stack_batches=stack)
    if stack == 1 and rnd.random() < 0.5:
        # device shuffle is single-batch by contract (the loader refuses the
        # stack_batches combination with a clear error)
        loader_kw.update(device_shuffle_capacity=rnd.choice([2, 3]),
                         device_shuffle_seed=rnd.randint(0, 9))
    seen = []

    def extend(u):
        ids = np.asarray(u["id"])
        if stack > 1:
            valid = np.asarray(u.get("_valid_rows", [ids.shape[1]] * stack))
            for k in range(ids.shape[0]):
                seen.extend(int(v) for v in ids[k][:int(valid[k])])
        else:
            n = int(np.asarray(u.get("_valid_rows", ids.shape[0])))
            seen.extend(int(v) for v in ids[:n])

    with make_batch_reader(url, **cfg) as r:
        with JaxDataLoader(r, **loader_kw) as loader:
            it = iter(loader)
            for _ in range(rnd.randint(0, 6)):
                try:
                    u = next(it)
                except StopIteration:
                    break
                extend(u)
            for u in loader.drain():
                extend(u)
            state = loader.state_dict()
    assert state["reader"]["ordinal_exact"], state
    with make_batch_reader(url, resume_from=state["reader"], **cfg) as r:
        with JaxDataLoader(r, **loader_kw) as loader:
            for u in loader:
                extend(u)
    return seen


def run_shards(make_batch_reader, url, cfg, rnd):
    union = []
    # one layout for BOTH shards: mixing shard modes across shards is an
    # invalid configuration, not a finding
    shard_mode = rnd.choice(["static", "epoch"])
    for s in range(2):
        with make_batch_reader(url, cur_shard=s, shard_count=2,
                               shard_mode=shard_mode,
                               **cfg) as r:
            union.extend(int(v) for b in r.iter_batches()
                         for v in b.columns["id"])
    return union


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=3600)
    ap.add_argument("--seed-base", type=int, default=0)
    ap.add_argument("--wedge-after", type=float, default=300)
    ap.add_argument("--dump", default="/tmp/fuzz_dump.txt")
    ap.add_argument("--root", default="/tmp/concurrency_fuzz")
    args = ap.parse_args()

    from petastorm_tpu.reader import make_batch_reader

    os.makedirs(args.root, exist_ok=True)
    datasets = build_datasets(args.root)
    progress = [0]
    start_progress_watchdog(progress, args.wedge_after, args.dump,
                            label="concurrency_fuzz")

    t0, i = time.time(), 0
    while time.time() - t0 < args.seconds:
        seed = args.seed_base + i
        rnd = random.Random(seed)
        url = rnd.choice(datasets)
        epochs = rnd.randint(1, 3)
        cfg = dict(
            reader_pool_type=rnd.choice(
                ["thread", "thread", "thread", "process", "serial"]),
            workers_count=rnd.choice([1, 2, 4, 8, 16]),
            num_epochs=epochs,
            shuffle_row_groups=rnd.random() < 0.8,
            shuffle_seed=rnd.randint(0, 999),
            results_queue_size=rnd.choice([2, 10]),
        )
        mode = rnd.choice(["plain", "resume", "resume", "shards", "loader"])
        try:
            if mode == "plain":
                seen = run_plain(make_batch_reader, url, cfg)
            elif mode == "resume":
                if cfg["reader_pool_type"] == "process":
                    cfg["reader_pool_type"] = "thread"  # keep resume fast
                seen = run_resume(make_batch_reader, url, cfg, rnd)
            elif mode == "loader":
                if cfg["reader_pool_type"] == "process":
                    cfg["reader_pool_type"] = "thread"
                seen = run_loader(make_batch_reader, url, cfg, rnd)
            else:
                seen = run_shards(make_batch_reader, url, cfg, rnd)
            counts = collections.Counter(seen)
            assert sorted(counts) == list(range(ROWS)), (
                f"seed {seed} {mode} {cfg}: missing/extra ids "
                f"{set(range(ROWS)) ^ set(counts)}")
            assert set(counts.values()) == {epochs}, (
                f"seed {seed} {mode} {cfg}: bad multiplicities "
                f"{ {k: v for k, v in counts.items() if v != epochs} }")
        except AssertionError:
            raise
        except Exception as exc:
            raise RuntimeError(f"seed {seed} {mode} {cfg} crashed") from exc
        progress[0] += 1
        i += 1
        if i % 20 == 0:
            print(f"iter {i} ok t={time.time() - t0:.0f}s", flush=True)
    print(f"done: {i} random configs, all invariants held", flush=True)


if __name__ == "__main__":
    main()
