"""Concurrency scaling under oversubscription.

This host has few cores, so 8-16 workers here exercise CONTENTION, ordering,
and leak behavior rather than speedup - exactly the properties that must hold
on real many-core TPU hosts.  Reference analog: the pool tests at
tests/test_workers_pool.py:19-60 (ventilate/consume across pool types).
"""

import collections
import gc

import numpy as np
import pytest

from petastorm_tpu.codecs import NdarrayCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.schema import Field, Schema

ROWS = 192  # 48 rowgroups x 4 rows


@pytest.fixture(scope="module")
def ds(tmp_path_factory):
    url = str(tmp_path_factory.mktemp("stress") / "ds")
    schema = Schema("Stress", [
        Field("id", np.int64),
        Field("payload", np.float32, (64,), NdarrayCodec()),
    ])
    write_dataset(url, schema,
                  [{"id": i, "payload": np.full(64, i, np.float32)}
                   for i in range(ROWS)],
                  row_group_size_rows=4)
    return url


@pytest.mark.parametrize("workers", [8, 16])
def test_thread_pool_oversubscribed_no_loss_no_dup(ds, workers):
    """16 threads on a small host: heavy GIL churn, out-of-order completion -
    the multiset and the ordinal-exact cursor must both survive."""
    for epochs in (1, 3):
        with make_batch_reader(ds, reader_pool_type="thread",
                               workers_count=workers, shuffle_seed=2,
                               num_epochs=epochs) as r:
            seen = [int(v) for b in r.iter_batches() for v in b.columns["id"]]
            state = r.state_dict()
        counts = collections.Counter(seen)
        assert sorted(counts) == list(range(ROWS))
        assert set(counts.values()) == {epochs}
        assert state["ordinal_exact"]
        assert state["position"] == epochs * 48  # exhausted = exact prefix


def test_process_pool_shm_arena_returns_to_baseline(ds):
    """8 spawn workers hammer the shm arena across 3 epochs; after the
    consumer drops its zero-copy views, every block must be back (no leak,
    no fragmentation lockup)."""
    with make_batch_reader(ds, reader_pool_type="process", workers_count=8,
                           num_epochs=3, shuffle_seed=3) as r:
        diag0 = r.diagnostics
        if not diag0.get("shm_transport"):
            pytest.skip("native shm arena unavailable on this host")
        baseline = diag0["shm_free_bytes"]
        seen = []
        for b in r.iter_batches():
            seen.append(np.asarray(b.columns["id"]).copy())
            del b
        gc.collect()
        diag = r.diagnostics
        assert diag["shm_free_bytes"] == baseline, "arena leaked blocks"
    counts = collections.Counter(int(v) for a in seen for v in a)
    assert sorted(counts) == list(range(ROWS))
    assert set(counts.values()) == {3}


def test_shard_mode_epoch_oversubscribed_no_loss(ds):
    """shard_mode='epoch' re-deals rowgroup ownership every epoch; under an
    oversubscribed thread pool the per-epoch partition property must hold
    regardless of completion order: the shards' union covers every row
    exactly once per epoch (so exactly num_epochs times overall), and
    ownership actually changes between epochs."""
    shards, epochs = 2, 2
    union = []
    for s in range(shards):
        with make_batch_reader(ds, reader_pool_type="thread", workers_count=8,
                               cur_shard=s, shard_count=shards,
                               shard_mode="epoch", shuffle_seed=7,
                               num_epochs=epochs) as r:
            union.extend(int(v) for b in r.iter_batches()
                         for v in b.columns["id"])
    counts = collections.Counter(union)
    assert sorted(counts) == list(range(ROWS))
    assert set(counts.values()) == {epochs}

    # the re-deal is real: shard 0's epoch-0 and epoch-1 rowgroup sets differ
    from petastorm_tpu.etl.metadata import open_dataset
    from petastorm_tpu.plan import ReadPlan

    plan = ReadPlan(open_dataset(ds).row_groups, shuffle_seed=7,
                    shard_index=0, shard_count=2, shard_mode="epoch")
    e0 = {it.row_group.global_index for it in plan.epoch_items(0)}
    e1 = {it.row_group.global_index for it in plan.epoch_items(1)}
    assert e0 != e1


def test_native_decode_fanout_matches_single_thread(tmp_path):
    """The batched native decoder's internal thread fan-out (nthreads=16,
    oversubscribed here) must be bit-identical to nthreads=1."""
    pytest.importorskip("cv2")
    from petastorm_tpu.native import image as native_image
    from petastorm_tpu.test_util.synthetic import synthetic_jpeg_bytes

    if not native_image.available():
        pytest.skip("native image library unavailable")
    bufs = synthetic_jpeg_bytes(64, 64, 96, quality=90)
    import pyarrow as pa

    col = pa.array(bufs, type=pa.binary())
    out1 = np.empty((64, 64, 96, 3), np.uint8)
    out16 = np.empty((64, 64, 96, 3), np.uint8)
    assert native_image.decode_column_native(col, out1, nthreads=1)
    assert native_image.decode_column_native(col, out16, nthreads=16)
    np.testing.assert_array_equal(out1, out16)

    # and the coefficient (entropy-only) fan-out too
    p1, q1, l1 = native_image.read_jpeg_coefficients_column(bufs, nthreads=1)
    p16, q16, l16 = native_image.read_jpeg_coefficients_column(bufs, nthreads=16)
    assert l1 == l16
    np.testing.assert_array_equal(q1, q16)
    for a, b in zip(p1, p16):
        np.testing.assert_array_equal(a, b)


def test_concurrency_fuzz_smoke(tmp_path):
    """A bounded slice of tools/concurrency_fuzz.py runs in CI: a dozen
    seeded random configurations (pool flavor x workers x epochs x
    consumption pattern), each asserting the exact-multiset invariant.
    The open-ended version is `python tools/concurrency_fuzz.py`."""
    import collections
    import random

    from petastorm_tpu.reader import make_batch_reader
    from tools import concurrency_fuzz as fuzz

    datasets = fuzz.build_datasets(str(tmp_path))
    for seed in range(12):
        rnd = random.Random(seed)
        url = rnd.choice(datasets)
        epochs = rnd.randint(1, 2)
        cfg = dict(reader_pool_type=rnd.choice(["thread", "thread", "serial"]),
                   workers_count=rnd.choice([1, 4, 8]),
                   num_epochs=epochs,
                   shuffle_row_groups=rnd.random() < 0.8,
                   shuffle_seed=rnd.randint(0, 999),
                   results_queue_size=rnd.choice([2, 10]))
        mode = rnd.choice(["plain", "resume", "shards"])
        if mode == "plain":
            seen = fuzz.run_plain(make_batch_reader, url, cfg)
        elif mode == "resume":
            seen = fuzz.run_resume(make_batch_reader, url, cfg, rnd)
        else:
            seen = fuzz.run_shards(make_batch_reader, url, cfg, rnd)
        counts = collections.Counter(seen)
        assert sorted(counts) == list(range(fuzz.ROWS)), (seed, mode, cfg)
        assert set(counts.values()) == {epochs}, (seed, mode, cfg)


def test_scaling_microbench_smoke(tmp_path):
    """The committed scaling microbench runs end-to-end and reports one JSON
    line per worker count."""
    import json

    from petastorm_tpu.benchmark import scaling

    url = str(tmp_path / "ds")
    scaling.build_dataset(url, rows=32, height=32, width=32)
    results = [scaling.measure(url, "thread", w, epochs=1) for w in (1, 8)]
    for res in results:
        assert res["samples"] == 32 and res["samples_per_sec"] > 0
        json.dumps(res)  # serializable
