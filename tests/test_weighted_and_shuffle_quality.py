"""WeightedSamplingReader + statistical shuffle-quality tests."""

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.test_util.shuffling_analysis import (analyze_shuffle_quality,
                                                        rank_correlation)
from petastorm_tpu.weighted_sampling import WeightedSamplingReader


def _make(url, tag, n=40):
    schema = Schema("W", [Field("id", np.int64), Field("src", np.dtype("object"))])
    write_dataset(url, schema, [{"id": i, "src": tag} for i in range(n)],
                  row_group_size_rows=10)


def test_weighted_mixing_ratio(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _make(a, "a", 400)
    _make(b, "b", 400)
    ra = make_reader(a, shuffle_row_groups=False, num_epochs=None,
                     reader_pool_type="serial")
    rb = make_reader(b, shuffle_row_groups=False, num_epochs=None,
                     reader_pool_type="serial")
    mixed = WeightedSamplingReader([ra, rb], [0.8, 0.2], seed=0)
    srcs = [next(mixed).src for _ in range(500)]
    mixed.stop(); mixed.join()
    frac_a = srcs.count("a") / len(srcs)
    assert 0.72 < frac_a < 0.88  # ~binomial(500, .8)


def test_weighted_exhausts_all(tmp_path):
    a, b = str(tmp_path / "a2"), str(tmp_path / "b2")
    _make(a, "a", 30)
    _make(b, "b", 20)
    with WeightedSamplingReader(
            [make_reader(a, shuffle_row_groups=False),
             make_reader(b, shuffle_row_groups=False)], [0.5, 0.5], seed=1) as mixed:
        rows = list(mixed)
    assert len(rows) == 50
    assert {r.src for r in rows} == {"a", "b"}


def test_weighted_schema_mismatch(tmp_path):
    a = str(tmp_path / "a3")
    _make(a, "a", 10)
    other = str(tmp_path / "c3")
    write_dataset(other, Schema("X", [Field("zzz", np.int64)]), [{"zzz": 1}])
    ra = make_reader(a)
    rc = make_reader(other)
    try:
        with pytest.raises(PetastormTpuError):
            WeightedSamplingReader([ra, rc], [0.5, 0.5])
    finally:
        for r in (ra, rc):
            r.stop(); r.join()


def test_weighted_validates_probabilities(tmp_path):
    a = str(tmp_path / "a4")
    _make(a, "a", 10)
    ra = make_reader(a)
    try:
        with pytest.raises(PetastormTpuError):
            WeightedSamplingReader([ra], [-1.0])
    finally:
        ra.stop(); ra.join()


# -- shuffle quality ----------------------------------------------------------

def test_rank_correlation_extremes():
    assert rank_correlation(np.arange(100)) == pytest.approx(1.0)
    assert rank_correlation(np.arange(100)[::-1]) == pytest.approx(-1.0)


@pytest.fixture(scope="module")
def ordered_ds(tmp_path_factory):
    url = str(tmp_path_factory.mktemp("sq") / "ordered")
    schema = Schema("O", [Field("id", np.int64)])
    write_dataset(url, schema, [{"id": i} for i in range(512)],
                  row_group_size_rows=16)
    return url


def test_shuffle_quality_improves_with_knobs(ordered_ds):
    # reference lesson (SURVEY.md section 4): statistical quality, not determinism
    rho_none = abs(analyze_shuffle_quality(ordered_ds, shuffle_row_groups=False))
    rho_groups = abs(analyze_shuffle_quality(ordered_ds, shuffle_row_groups=True))
    rho_full = abs(analyze_shuffle_quality(ordered_ds, shuffle_row_groups=True,
                                           shuffle_row_drop_partitions=4,
                                           shuffling_queue_capacity=128))
    assert rho_none == pytest.approx(1.0)
    assert rho_groups < 0.5         # rowgroup shuffle decorrelates coarsely
    assert rho_full < rho_none
    assert rho_full < 0.2           # buffer + row-drop approaches uniform


def test_device_buffer_shuffle_quality(tmp_path):
    """Statistical check (SURVEY.md section 4 lesson 5): the HBM exchange
    buffer decorrelates read order, not just permutes within batches."""
    import numpy as np

    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.test_util.shuffling_analysis import rank_correlation

    url = str(tmp_path / "ds")
    write_dataset(url, Schema("Q", [Field("id", np.int64)]),
                  [{"id": i} for i in range(256)], row_group_size_rows=8)

    def read_order(capacity):
        with make_batch_reader(url, shuffle_row_groups=False,
                               reader_pool_type="serial", num_epochs=1) as r:
            with JaxDataLoader(r, batch_size=8, fields=["id"],
                               device_shuffle_capacity=capacity,
                               device_shuffle_seed=11) as loader:
                return np.asarray([int(v) for b in loader
                                   for v in np.asarray(b["id"])])

    assert abs(rank_correlation(np.arange(256))) > 0.99  # sequential baseline
    shuffled = abs(rank_correlation(read_order(8)))
    assert shuffled < 0.5, shuffled


def test_weighted_mixing_feeds_jax_loader(tmp_path):
    """WeightedSamplingReader satisfies the loader's reader contract: mixed
    datasets flow to the device as one stream."""
    import numpy as np

    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.weighted_sampling import WeightedSamplingReader

    schema = Schema("M", [Field("source", np.int64), Field("v", np.float32)])

    def make(name, source, n):
        url = str(tmp_path / name)
        write_dataset(url, schema,
                      [{"source": source, "v": float(i)} for i in range(n)],
                      row_group_size_rows=8)
        return url

    ra = make_batch_reader(make("a", 0, 64), num_epochs=None,
                           reader_pool_type="serial")
    rb = make_batch_reader(make("b", 1, 64), num_epochs=None,
                           reader_pool_type="serial")
    mixed = WeightedSamplingReader([ra, rb], [0.7, 0.3], seed=4)
    sources = []
    with JaxDataLoader(mixed, batch_size=16) as loader:
        it = iter(loader)
        for _ in range(24):
            sources.extend(int(v) for v in np.asarray(next(it)["source"]))
    frac_b = np.mean(np.asarray(sources) == 1)
    assert 0.15 < frac_b < 0.45, frac_b  # ~0.3 mixing ratio reaches the device


def _shard_read_order(url, shard, count, seed):
    """ids one pod host (shard) delivers, with every shuffle stage a real host
    runs: rowgroup permutation + row-drop partitions (reader, shuffle_seed) and
    the host shuffling buffer (loader, per-host buffer_seed)."""
    from petastorm_tpu.jax.loader import JaxDataLoader

    reader = make_reader(url, schema_fields=["id"], cur_shard=shard,
                         shard_count=count, shuffle_row_groups=True,
                         shuffle_row_drop_partitions=2, shuffle_seed=seed,
                         reader_pool_type="serial")
    ids = []
    with JaxDataLoader(reader, batch_size=16, drop_last=False,
                       shuffling_queue_capacity=128,
                       buffer_seed=seed * 1000 + shard) as loader:
        for b in loader:
            ids.extend(np.asarray(b["id"]).tolist())
    return ids


@pytest.fixture(scope="module")
def pod_ordered_ds(tmp_path_factory):
    url = str(tmp_path_factory.mktemp("sq_pod") / "ordered")
    schema = Schema("O", [Field("id", np.int64)])
    write_dataset(url, schema, [{"id": i} for i in range(2048)],
                  row_group_size_rows=16)
    return url


def test_pod_scale_shuffle_quality(pod_ordered_ds):
    """Shuffle quality AT POD SCALE.  8 simulated shards,
    two epochs with different seeds: every shard's own stream AND the
    concatenated global stream must decorrelate from the written order
    (explicit rank-correlation thresholds), shards must partition the dataset
    exactly, different seeds must produce a different global order, and the
    same seed must reproduce it (determinism).  Reference analog:
    petastorm/test_util/shuffling_analysis.py:30-52 (single-reader only -
    the reference never measures the sharded case)."""
    SHARDS = 8
    epochs = {}
    for seed in (3, 4):
        per_shard = [_shard_read_order(pod_ordered_ds, k, SHARDS, seed)
                     for k in range(SHARDS)]
        # each shard's stream is well shuffled on its own (the signal a
        # single host's training loop sees)
        for k, ids in enumerate(per_shard):
            rho = abs(rank_correlation(np.asarray(ids)))
            assert rho < 0.35, f"seed {seed} shard {k}: |rho|={rho:.3f}"
        # shards partition the dataset exactly: nothing lost, nothing doubled
        assert sorted(i for ids in per_shard for i in ids) == list(range(2048))
        # the global stream AS A POD DELIVERS IT: hosts step in lockstep, so
        # global batch t is [shard0 rows t, shard1 rows t, ...] - interleave
        # row-wise (plain concatenation would let the seed-INDEPENDENT shard
        # assignment dominate the position variance and mask the seed effect)
        assert len({len(ids) for ids in per_shard}) == 1
        flat = [i for row in zip(*per_shard) for i in row]
        rho_g = abs(rank_correlation(np.asarray(flat)))
        assert rho_g < 0.25, f"seed {seed}: global |rho|={rho_g:.3f}"
        epochs[seed] = flat

    # different seeds -> genuinely different global orders: correlate the
    # POSITION of each id across the two epochs
    pos = {s: np.empty(2048, dtype=np.int64) for s in epochs}
    for s, flat in epochs.items():
        for p, i in enumerate(flat):
            pos[s][i] = p
    cross = abs(rank_correlation(pos[4][np.argsort(pos[3])]))
    assert cross < 0.25, f"epoch orders correlate: |rho|={cross:.3f}"

    # determinism lives at the PLAN layer: the seeded reader stream (no host
    # shuffling buffer - its interleaving is deliberately timing-dependent,
    # bounded by min_after) reproduces exactly for the same seed/shard
    def plan_order(shard, seed):
        reader = make_reader(pod_ordered_ds, schema_fields=["id"],
                             cur_shard=shard, shard_count=SHARDS,
                             shuffle_row_groups=True,
                             shuffle_row_drop_partitions=2, shuffle_seed=seed,
                             reader_pool_type="serial")
        with reader:
            return [int(i) for cb in reader.iter_batches()
                    for i in np.asarray(cb.columns["id"])]

    assert plan_order(5, 3) == plan_order(5, 3)
    assert plan_order(5, 3) != plan_order(5, 4)
