"""Feature matrix under the PROCESS pool + shm transport.

The reference runs its full behavior matrix across every pool flavor
(tests/test_end_to_end.py:44-59).  Spawn costs ~1-3 s/worker on the 1-core CI
host, so the cells here are the representative behaviors whose code paths
differ under process isolation: predicate split-read, ngram window formation,
local-disk cache reuse across epochs, and quiesce-exact resume cursors - all
crossing the C++ shm arena instead of in-process queues.
"""

import collections

import numpy as np
import pytest

from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.ngram import NGram
from petastorm_tpu.predicates import in_lambda
from petastorm_tpu.reader import make_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.test_util.synthetic import create_test_dataset

WORKERS = 2


def _div3(cols):
    return cols["id"] % 3 == 0


#: module-level (spawn workers pickle the predicate; locals cannot cross)
DIV3 = in_lambda(["id"], _div3, vectorized=True)


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("pp_e2e") / "ds")
    rows = create_test_dataset(path, num_rows=48, row_group_size_rows=8)
    return path, rows


@pytest.fixture(scope="module")
def seq_dataset(tmp_path_factory):
    url = str(tmp_path_factory.mktemp("pp_seq") / "seq")
    schema = Schema("Seq", [
        Field("ts", np.int64, (), ScalarCodec()),
        Field("cam", np.uint8, (8, 8), NdarrayCodec()),
    ])
    rng = np.random.default_rng(2)
    write_dataset(url, schema,
                  [{"ts": i, "cam": rng.integers(0, 255, (8, 8), dtype=np.uint8)}
                   for i in range(64)],
                  row_group_size_rows=16)
    return url


def test_predicate_split_read_under_process_pool(dataset):
    """Predicate split-read (predicate cols decode first, mask pre-decode)
    with the mask crossing the shm transport."""
    url, rows = dataset
    with make_reader(url, reader_pool_type="process", workers_count=WORKERS,
                     predicate=DIV3, shuffle_row_groups=False) as r:
        got = sorted(row.id for row in r)
    assert got == [i for i in range(48) if i % 3 == 0]


def test_ngram_windows_under_process_pool(seq_dataset):
    """NGram window formation inside spawned workers; windows (nested column
    naming) must survive the shm hop intact."""
    ng = NGram({0: ["ts", "cam"], 1: ["ts", "cam"]}, delta_threshold=1,
               timestamp_field="ts")
    with make_reader(seq_dataset, ngram=ng, reader_pool_type="process",
                     workers_count=WORKERS, num_epochs=1,
                     shuffle_row_groups=False) as r:
        windows = list(r)
    assert len(windows) == 64 - 16 // 16 * 4  # 4 rowgroups x (16-1) windows
    for w in windows:
        assert w[1].ts == w[0].ts + 1
        assert w[0].cam.shape == (8, 8)


def test_local_disk_cache_under_process_pool(dataset, tmp_path):
    """cache_type='local-disk' is the documented cache for process pools
    (memory cache is refused there): epoch 2 must serve identical rows and
    the cache directory must hold entries written by the spawned workers."""
    url, rows = dataset
    cache_dir = str(tmp_path / "cache")
    with make_reader(url, reader_pool_type="process", workers_count=WORKERS,
                     cache_type="local-disk", cache_location=cache_dir,
                     num_epochs=2, shuffle_row_groups=False,
                     schema_fields=["id", "matrix"]) as r:
        ids = [row.id for row in r]
    counts = collections.Counter(ids)
    assert sorted(counts) == list(range(48)) and set(counts.values()) == {2}
    import os

    cached = [f for _, _, fs in os.walk(cache_dir) for f in fs]
    assert cached, "local-disk cache wrote nothing"


def test_quiesce_exact_resume_under_process_pool(tmp_path):
    """quiesce() -> exhaust -> state_dict() must be an EXACT cursor even with
    spawned workers completing items out of ventilation order (ordinals ride
    the shm transport); the resumed reader replays the rest exactly once."""
    # many small rowgroups so the bounded in-flight window cannot swallow the
    # whole epoch before quiesce
    url = str(tmp_path / "resume_ds")
    create_test_dataset(url, num_rows=48, row_group_size_rows=2)
    seen = []
    with make_reader(url, reader_pool_type="process", workers_count=WORKERS,
                     results_queue_size=2, num_epochs=1, shuffle_seed=11,
                     schema_fields=["id"]) as r:
        it = iter(r)
        for _ in range(10):
            seen.append(next(it).id)
        r.quiesce()
        for row in it:
            seen.append(row.id)
        state = r.state_dict()
    assert state["ordinal_exact"]
    resumed = []
    with make_reader(url, reader_pool_type="process", workers_count=WORKERS,
                     num_epochs=1, shuffle_seed=11, schema_fields=["id"],
                     resume_from=state) as r:
        resumed = [row.id for row in r]
    counts = collections.Counter(seen + resumed)
    assert sorted(counts) == list(range(48)) and max(counts.values()) == 1
    assert resumed, "quiesce consumed the whole epoch; nothing left to resume"
