"""Remote IO under injected latency.

``memory://`` and local disk answer in microseconds; real object stores
charge 10-50 ms per request.  These tests run the production remote code
path (PyFileSystem => ``pre_buffer=True``, ``io_retries='auto'`` armed)
against ``test_util.latency_fs`` and assert the three claims:

1. coalescing: a rowgroup's column chunks arrive in FEW ranged reads -
   bounded per rowgroup, NOT one read per column;
2. latency hiding: with N workers + prefetch the injected sleep overlaps
   itself and decode, so wall time stays far under the serial sum of
   injected latency (and within a stated factor of the local read);
3. retries: ``io_retries`` composes with slow-then-FAILING calls.

Reference analog: petastorm/fs_utils.py:88-126 and the S3
eventual-consistency machinery (spark_dataset_converter.py:565-595) exist
because remote stores are slow and flaky, but the reference never tests
under injected latency.
"""

import time

import numpy as np
import pytest

from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.test_util.latency_fs import latent_filesystem
from petastorm_tpu.test_util.synthetic import write_wide_dataset

N_COLS = 8
N_ROWGROUPS = 8
ROWS_PER_RG = 32


@pytest.fixture(scope="module")
def wide_ds(tmp_path_factory):
    """Many-column dataset: the shape where per-column reads would hurt."""
    url = str(tmp_path_factory.mktemp("latent") / "wide")
    write_wide_dataset(url, n_cols=N_COLS, n_rowgroups=N_ROWGROUPS,
                       rows_per_rg=ROWS_PER_RG)
    return url


def _read_all(url, fs, **kwargs):
    ids = []
    with make_batch_reader(url, filesystem=fs, shuffle_row_groups=False,
                           num_epochs=1, **kwargs) as r:
        for cb in r.iter_batches():
            ids.extend(np.asarray(cb.columns["id"]).astype(int).tolist())
    return ids


def test_reads_per_rowgroup_bounded(wide_ds):
    """The coalescing claim, counted: pre_buffer must merge each rowgroup's
    column chunks into a few ranged reads.  Zero latency here - this test
    is purely about CALL COUNT."""
    fs, stats = latent_filesystem(latency_s=0.0)
    ids = _read_all(wide_ds, fs, reader_pool_type="serial")
    assert sorted(ids) == list(range(N_ROWGROUPS * ROWS_PER_RG))
    s = stats.snapshot()
    # footer + metadata cost a handful of reads once per FILE; the per-
    # rowgroup marginal cost is what scales with dataset size.  8 columns
    # x 8 rowgroups = 64 column chunks: uncoalesced would be >= 64 reads
    # before any footer traffic.
    reads_per_rg = s["reads"] / N_ROWGROUPS
    assert reads_per_rg < N_COLS / 2, (
        f"{s['reads']} reads for {N_ROWGROUPS} rowgroups of {N_COLS} columns"
        f" ({reads_per_rg:.1f}/rowgroup) - column chunks are not coalesced")
    assert s["opens"] <= 4, s  # file opened once (+ metadata passes), cached


def test_latency_hidden_by_workers_and_prefetch(wide_ds):
    """With 20 ms per remote call, N workers + pre_buffer must OVERLAP the
    waits: wall time stays well under the serial sum of injected sleeps,
    and within a stated factor of the zero-latency read."""
    t0 = time.perf_counter()
    fs0, _ = latent_filesystem(latency_s=0.0)
    ids = _read_all(wide_ds, fs0, reader_pool_type="thread", workers_count=4)
    local_wall = time.perf_counter() - t0
    assert sorted(ids) == list(range(N_ROWGROUPS * ROWS_PER_RG))

    fs, stats = latent_filesystem(latency_s=0.02)
    t0 = time.perf_counter()
    ids = _read_all(wide_ds, fs, reader_pool_type="thread", workers_count=4)
    wall = time.perf_counter() - t0
    assert sorted(ids) == list(range(N_ROWGROUPS * ROWS_PER_RG))
    s = stats.snapshot()
    assert s["slept_s"] > 0.2, s  # the latency was really injected
    # paid serially, the injected sleeps alone would take slept_s; workers
    # and pre_buffer's up-front ranged reads must overlap them
    assert wall < 0.75 * s["slept_s"] + local_wall + 0.5, (
        f"wall {wall:.2f}s vs {s['slept_s']:.2f}s injected sleep"
        f" (local {local_wall:.2f}s) - remote latency is being paid"
        " serially, not hidden")
    # and the end-to-end factor vs local stays bounded (stated factor: the
    # latent read may cost up to 6x the local wall on this 1-core box; a
    # per-column-read regression would blow far past it)
    assert wall < 6.0 * local_wall + 1.0, (
        f"latent/local = {wall / max(local_wall, 1e-6):.1f}x")


def test_io_retries_compose_with_slow_failing_calls(wide_ds):
    """Slow-then-failing remote reads: the first 3 reads sleep 20 ms then
    raise OSError; io_retries='auto' (armed for non-local filesystems) must
    absorb them and deliver every row exactly once."""
    fs, stats = latent_filesystem(latency_s=0.02, fail_first_reads=3)
    ids = _read_all(wide_ds, fs, reader_pool_type="serial")
    assert sorted(ids) == list(range(N_ROWGROUPS * ROWS_PER_RG))
    s = stats.snapshot()
    assert s["failures_injected"] == 3, s


def test_io_retries_off_surfaces_failure(wide_ds):
    """io_retries=0 on the same slow-failing filesystem surfaces the
    OSError instead of silently retrying - the knob is real."""
    fs, _ = latent_filesystem(latency_s=0.0, fail_first_reads=50)
    with pytest.raises((OSError, PetastormTpuError)):
        _read_all(wide_ds, fs, reader_pool_type="serial", io_retries=0)


def test_row_reader_over_latent_fs(wide_ds):
    """The row path (make_reader) works over the latent filesystem too -
    the wrapper is a real pyarrow filesystem, not a parquet-only shim."""
    fs, stats = latent_filesystem(latency_s=0.005)
    with make_reader(wide_ds, filesystem=fs, shuffle_row_groups=False,
                     num_epochs=1, reader_pool_type="serial",
                     schema_fields=["id"]) as r:
        ids = [int(row.id) for row in r]
    assert sorted(ids) == list(range(N_ROWGROUPS * ROWS_PER_RG))
    assert stats.snapshot()["reads"] > 0
