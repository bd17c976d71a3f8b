"""Transient remote-IO resilience.

A TPU pod reading an object store sees transient 5xx/timeout errors as
weather; one such error mid-epoch must not kill the reader.  These tests
inject OSError failures into an fsspec ``memory://`` store (the same fallback
branch a real object store without pyarrow-native support takes) and assert
the epoch completes with the row multiset intact and the cursor exact.

Reference anchors: HDFS failover-retry (hdfs/namenode.py:244-299), stub-worker
fault-injection style (workers_pool/tests/stub_workers.py:66-68).
"""

import numpy as np
import pytest

from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.etl.metadata import open_dataset
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_reader
from petastorm_tpu.retry import (RetryPolicy, resolve_retry_policy, retry_call)
from petastorm_tpu.schema import Field, Schema

fsspec = pytest.importorskip("fsspec")

FAST = RetryPolicy(max_attempts=4, initial_backoff_s=0.01, max_backoff_s=0.02)


# -- retry_call unit behavior -------------------------------------------------

def test_retry_call_retries_transient_then_succeeds():
    calls, slept = [], []
    def fn():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("simulated 503")
        return "ok"
    assert retry_call(fn, FAST, sleep=slept.append) == "ok"
    assert len(calls) == 3 and len(slept) == 2
    assert all(s > 0 for s in slept)


def test_retry_call_exhausts_budget():
    def fn():
        raise TimeoutError("still down")  # OSError subclass
    with pytest.raises(TimeoutError):
        retry_call(fn, FAST, sleep=lambda s: None)


def test_retry_call_does_not_retry_durable_errors():
    calls = []
    def fn():
        calls.append(1)
        raise FileNotFoundError("gone")
    with pytest.raises(FileNotFoundError):
        retry_call(fn, FAST, sleep=lambda s: None)
    assert len(calls) == 1  # no second attempt


def test_retry_call_none_policy_is_passthrough():
    calls = []
    def fn():
        calls.append(1)
        raise OSError("boom")
    with pytest.raises(OSError):
        retry_call(fn, None, sleep=lambda s: None)
    assert len(calls) == 1


def test_resolve_policy_auto_local_off_remote_on(tmp_path):
    import pyarrow.fs as pafs

    assert resolve_retry_policy("auto", pafs.LocalFileSystem()) is None
    remote = pafs.PyFileSystem(pafs.FSSpecHandler(fsspec.filesystem("memory")))
    assert isinstance(resolve_retry_policy("auto", remote), RetryPolicy)
    assert resolve_retry_policy(None, remote) is None
    assert resolve_retry_policy(6, remote).max_attempts == 6
    assert resolve_retry_policy(FAST, remote) is FAST
    with pytest.raises(PetastormTpuError):
        resolve_retry_policy("always", remote)
    with pytest.raises(PetastormTpuError):
        RetryPolicy(max_attempts=0)


# -- end-to-end fault injection over memory:// --------------------------------

SCHEMA = Schema("Flaky", [Field("id", np.int64),
                          Field("x", np.float32, (3,))])
N_ROWS = 32


@pytest.fixture()
def flaky_ds():
    memfs = fsspec.filesystem("memory")
    url = "memory://flaky_ds"
    rng = np.random.default_rng(0)
    # rows_per_file=8 -> 4 separate files, so mid-epoch failures hit fresh
    # open() calls (the worker caches one ParquetFile per file)
    write_dataset(url, SCHEMA,
                  [{"id": i, "x": rng.standard_normal(3).astype(np.float32)}
                   for i in range(N_ROWS)],
                  row_group_size_rows=4, rows_per_file=8)
    orig_open = memfs.open
    state = {"fail_reads": 0, "failed": 0}

    def flaky_open(path, mode="rb", **kw):
        if "r" in mode and state["fail_reads"] > 0:
            state["fail_reads"] -= 1
            state["failed"] += 1
            raise OSError(f"simulated transient 503 opening {path}")
        return orig_open(path, mode, **kw)

    memfs.open = flaky_open
    try:
        yield url, state
    finally:
        memfs.open = orig_open
        memfs.store.clear()


def test_mid_epoch_transient_read_recovers_exactly(flaky_ds):
    """Transient open failures mid-epoch: every row delivered exactly once,
    and the end-of-epoch cursor is exact (no loss, no duplication)."""
    url, state = flaky_ds
    with make_reader(url, reader_pool_type="serial", num_epochs=1,
                     shuffle_row_groups=False, io_retries=FAST) as r:
        it = iter(r)
        first = [next(it).id for _ in range(4)]   # one file's worth, cleanly
        state["fail_reads"] = 3                   # then the weather rolls in
        rest = [row.id for row in it]
        state_dict = r.state_dict()
    assert state["failed"] >= 1                   # injection really fired
    assert sorted(first + rest) == list(range(N_ROWS))
    assert state_dict["ordinal_exact"]


def test_exhausted_retries_surface_the_error(flaky_ds):
    url, state = flaky_ds
    state["fail_reads"] = 10**6                   # outage, not weather
    policy = RetryPolicy(max_attempts=2, initial_backoff_s=0.01,
                         max_backoff_s=0.01)
    with pytest.raises(OSError, match="503"):
        with make_reader(url, reader_pool_type="serial", num_epochs=1,
                         shuffle_row_groups=False, io_retries=policy) as r:
            list(r)


def test_io_retries_disabled_fails_fast(flaky_ds):
    url, state = flaky_ds
    with pytest.raises(OSError, match="503"):
        with make_reader(url, reader_pool_type="serial", num_epochs=1,
                         shuffle_row_groups=False, io_retries=None) as r:
            # inject AFTER construction so the failure hits a worker read,
            # not the metadata open (whose _common_metadata probe degrades
            # gracefully by design)
            state["fail_reads"] = 1
            list(r)
    assert state["fail_reads"] == 0               # exactly one attempt, no retry


def test_metadata_open_retries_injected_open_failures(tmp_path):
    """latency_fs ``fail_first_opens``: the metadata-open path (footer/KV
    reads through ``open_input_file``) really exercises the retry policy -
    not just the per-read path."""
    from petastorm_tpu.test_util.latency_fs import latent_filesystem

    url = str(tmp_path / "ds")
    write_dataset(url, SCHEMA,
                  [{"id": i, "x": np.zeros(3, np.float32)} for i in range(8)],
                  row_group_size_rows=4)
    fs, stats = latent_filesystem(latency_s=0.0, fail_first_opens=2)
    info = open_dataset(url, filesystem=fs, io_retries=FAST)
    assert sum(rg.num_rows for rg in info.row_groups) == 8
    assert stats.failures_injected >= 2


def test_metadata_open_failures_fail_fast_without_retries(tmp_path):
    from petastorm_tpu.test_util.latency_fs import latent_filesystem

    url = str(tmp_path / "ds")
    write_dataset(url, SCHEMA,
                  [{"id": i, "x": np.zeros(3, np.float32)} for i in range(8)],
                  row_group_size_rows=4)
    # >1: the first failure may land on the _common_metadata probe, which
    # degrades gracefully by design; later ones hit required footer opens
    fs, _stats = latent_filesystem(latency_s=0.0, fail_first_opens=4)
    with pytest.raises(OSError, match="injected transient open failure"):
        open_dataset(url, filesystem=fs, io_retries=None)


def test_retries_are_counted_in_telemetry(flaky_ds):
    """Satellite: retry_call retries surface as ``io.retries`` counters (per
    category) and as trace events carrying the full ``what`` label - visible
    in the diagnose report, not only in log warnings."""
    from petastorm_tpu.telemetry import Telemetry

    url, state = flaky_ds
    tele = Telemetry()
    with make_reader(url, reader_pool_type="serial", num_epochs=1,
                     shuffle_row_groups=False, io_retries=FAST,
                     telemetry=tele) as r:
        it = iter(r)
        first = [next(it).id for _ in range(4)]
        state["fail_reads"] = 2
        rest = [row.id for row in it]
    assert sorted(first + rest) == list(range(N_ROWS))
    counters = tele.snapshot()["counters"]
    assert counters["io.retries"] >= 2
    per_cat = {k: v for k, v in counters.items()
               if k.startswith("io.retries.")}
    assert per_cat, "expected a per-category io.retries.<what> counter"
    events = tele.chrome_trace()["traceEvents"]
    retry_events = [e for e in events if e.get("name") == "io-retry"]
    assert retry_events and "what" in retry_events[0]["args"]
    # and the human-readable report names the fault section
    assert "io.retries" in tele.pipeline_report()


def test_metadata_open_retries_listing_failures():
    memfs = fsspec.filesystem("memory")
    url = "memory://flaky_meta"
    write_dataset(url, SCHEMA,
                  [{"id": i, "x": np.zeros(3, np.float32)} for i in range(8)],
                  row_group_size_rows=4)
    orig_info = memfs.info
    state = {"fail": 2}

    def flaky_info(path, **kw):
        if state["fail"] > 0:
            state["fail"] -= 1
            raise OSError("simulated transient 503 on info")
        return orig_info(path, **kw)

    memfs.info = flaky_info
    try:
        info = open_dataset(url, io_retries=FAST)
        assert sum(rg.num_rows for rg in info.row_groups) == 8
        assert state["fail"] == 0
    finally:
        memfs.info = orig_info
        memfs.store.clear()
