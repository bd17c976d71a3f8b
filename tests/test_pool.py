"""Executor pool tests across all flavors with stub workers.

Reference model: petastorm/workers_pool/tests/test_workers_pool.py:19-60 - one
shared test impl parametrized over pools; exception propagation; ventilator
semantics tested separately (test_ventilator.py).
"""

import queue
import time

import pytest

from petastorm_tpu.errors import ReaderClosedError
from petastorm_tpu.etl.metadata import RowGroupRef
from petastorm_tpu.plan import ReadPlan
from petastorm_tpu.pool import (SerialExecutor, ThreadedExecutor, Ventilator,
                                WorkerError, make_executor)
from petastorm_tpu.test_util.stub_workers import (ExplodingWorker, MultiplierWorker,
                                                  PidWorker, SleepyWorker)

ALL_KINDS = ["serial", "thread", "process"]
FAST_KINDS = ["serial", "thread"]


def _collect(executor, n, timeout=30):
    out = []
    deadline = time.monotonic() + timeout
    while len(out) < n:
        remaining = deadline - time.monotonic()
        assert remaining > 0, f"timed out with {len(out)}/{n} results"
        try:
            out.append(executor.get(timeout=min(remaining, 0.5)))
        except queue.Empty:
            continue
    return out


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_roundtrip_all_flavors(kind):
    with make_executor(kind, workers_count=2) as ex:
        ex.start(MultiplierWorker(3))
        for i in range(10):
            ex.put(i)
        results = _collect(ex, 10)
    assert sorted(results) == [i * 3 for i in range(10)]


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_exception_propagates(kind):
    with make_executor(kind, workers_count=2) as ex:
        ex.start(ExplodingWorker(trigger=3))
        for i in range(5):
            ex.put(i)
        with pytest.raises((WorkerError, RuntimeError)) as ei:
            _collect(ex, 5)
        assert "boom" in str(ei.value)


def test_process_pool_real_isolation():
    import os
    with make_executor("process", workers_count=2) as ex:
        ex.start(PidWorker())
        for i in range(4):
            ex.put(i)
        pids = set(_collect(ex, 4))
    assert os.getpid() not in pids
    assert 1 <= len(pids) <= 2


def test_thread_pool_parallelism():
    with ThreadedExecutor(workers_count=4) as ex:
        ex.start(SleepyWorker(0.05))
        t0 = time.monotonic()
        for i in range(8):
            ex.put(i)
        _collect(ex, 8)
        elapsed = time.monotonic() - t0
    assert elapsed < 8 * 0.05  # must overlap sleeps


def test_put_after_stop_raises():
    ex = SerialExecutor()
    ex.start(MultiplierWorker(1))
    ex.stop()
    with pytest.raises(ReaderClosedError):
        ex.put(1)


def test_diagnostics():
    with ThreadedExecutor(workers_count=2) as ex:
        ex.start(MultiplierWorker(1))
        ex.put(1)
        ex.get(timeout=5)
        d = ex.diagnostics
        assert d["ventilated"] == 1 and d["consumed"] == 1
        assert d["workers_count"] == 2


def test_workers_busy_heartbeat_names_stuck_item():
    """A wedged worker is attributable: diagnostics report (worker index,
    item ordinal, seconds stuck) while it is inside fn (stall
    diagnostics)."""
    import threading

    from petastorm_tpu.pool import VentilatedItem
    from petastorm_tpu.test_util.stub_workers import BlockingWorker

    release = threading.Event()
    with ThreadedExecutor(workers_count=2) as ex:
        ex.start(BlockingWorker(release, trigger=7))
        ex.put(VentilatedItem(7, 7))
        deadline = time.monotonic() + 10
        busy = []
        while time.monotonic() < deadline:
            busy = ex.diagnostics["workers_busy"]
            if busy:
                break
            time.sleep(0.02)
        assert busy, "stuck worker never appeared in workers_busy"
        (_idx, ordinal, stuck_s) = busy[0]
        assert ordinal == 7 and stuck_s >= 0
        release.set()
        got = ex.get(timeout=10)
        assert got.item == 7
        # after completion the heartbeat clears
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and ex.diagnostics["workers_busy"]:
            time.sleep(0.02)
        assert ex.diagnostics["workers_busy"] == []


def test_process_pool_workers_busy_heartbeat():
    """The heartbeat contract crosses the process boundary: a worker busy
    inside fn shows up in workers_busy with its item ordinal, via the
    lock-free shared slots (docs/operations.md stall diagnostics)."""
    from petastorm_tpu.pool import VentilatedItem, _ProcessExecutor
    from petastorm_tpu.test_util.stub_workers import SleepyWorker

    with _ProcessExecutor(workers_count=1) as ex:
        ex.start(SleepyWorker(4.0))
        ex.put(VentilatedItem(9, "x"))
        deadline = time.monotonic() + 30
        busy = []
        while time.monotonic() < deadline:
            busy = ex.diagnostics.get("workers_busy", [])
            if busy:
                break
            time.sleep(0.1)
        assert busy and busy[0][:2] == (0, 9) and busy[0][2] >= 0, busy
        got = ex.get(timeout=60)
        assert got.item == "x"
        # idle again once the result is delivered
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and ex.diagnostics.get("workers_busy")):
            time.sleep(0.05)
        assert ex.diagnostics.get("workers_busy") == []


def test_reader_stall_warns_and_aborts(tmp_path, monkeypatch, caplog):
    """A pipeline that stops producing results warns with the pipeline state
    and (with PETASTORM_TPU_STALL_ABORT_S) raises instead of wedging."""
    import logging
    import threading

    import numpy as np

    from petastorm_tpu import reader as reader_mod
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.transform import TransformSpec

    url = str(tmp_path / "ds")
    schema = Schema("S", [Field("x", np.int64)])
    write_dataset(url, schema, [{"x": i} for i in range(8)],
                  row_group_size_rows=4)

    release = threading.Event()

    def wedge(cols):
        release.wait()
        return cols

    monkeypatch.setattr(reader_mod, "_STALL_WARN_S", 0.3)
    monkeypatch.setattr(reader_mod, "_STALL_ABORT_S", 1.5)
    t0 = time.monotonic()
    try:
        with make_batch_reader(url, reader_pool_type="thread",
                               workers_count=1, shuffle_row_groups=False,
                               transform_spec=TransformSpec(wedge)) as r:
            with caplog.at_level(logging.WARNING,
                                 logger="petastorm_tpu.reader"):
                with pytest.raises(WorkerError) as ei:
                    next(iter(r.iter_batches()))
            assert "workers_busy" in str(ei.value)
            assert any("no batch" in rec.message for rec in caplog.records)
        # the exit above must NOT wedge on joining the still-blocked worker:
        # after a stall abort the executor join is bounded and abandons it
        # (daemonic), logging what it abandoned
        assert time.monotonic() - t0 < 30
    finally:
        release.set()  # let the abandoned daemon thread finish and exit


def _plan(n=6):
    rgs = [RowGroupRef(f"/f{i}", 0, 5, i) for i in range(n)]
    return ReadPlan(rgs, shuffle_row_groups=False)


def test_ventilator_single_epoch():
    with ThreadedExecutor(workers_count=2) as ex:
        ex.start(SleepyWorker(0))
        vent = Ventilator(ex, _plan(6), num_epochs=1)
        assert vent.total_items == 6
        vent.start()
        results = _collect(ex, 6)
        vent.join()
    assert len(results) == 6


def test_ventilator_multi_epoch():
    with ThreadedExecutor(workers_count=2) as ex:
        ex.start(SleepyWorker(0))
        vent = Ventilator(ex, _plan(4), num_epochs=3)
        assert vent.total_items == 12
        vent.start()
        results = _collect(ex, 12)
        vent.join()
    assert len(results) == 12


def test_ventilator_infinite_stops_cleanly():
    with ThreadedExecutor(workers_count=2) as ex:
        ex.start(SleepyWorker(0))
        vent = Ventilator(ex, _plan(4), num_epochs=None)
        assert vent.total_items is None
        vent.start()
        _collect(ex, 20)  # well past one epoch
        vent.stop()
        ex.stop()
        vent.join()


def test_ventilator_backpressure():
    # bounded in-queue: ventilator must not race ahead of consumption
    ex = ThreadedExecutor(workers_count=1, in_queue_size=2, results_queue_size=2)
    with ex:
        ex.start(SleepyWorker(0))
        vent = Ventilator(ex, _plan(50), num_epochs=1)
        vent.start()
        time.sleep(0.3)
        # at most in_queue(2) + results(2) + 1 in-hand can be in flight
        assert ex.diagnostics["ventilated"] <= 6
        _collect(ex, 50)
        vent.join()


def test_process_pool_hard_crash_surfaces_not_hangs():
    """A worker process dying WITHOUT a traceback (OOM-kill, segfault) must
    surface as a WorkerError at the consumer, not an indefinite hang
    (reference has no coverage for this; its zmq pool would wait forever)."""
    from petastorm_tpu.test_util.stub_workers import HardCrashWorker

    ex = make_executor("process", workers_count=2)
    try:
        ex.start(HardCrashWorker(trigger=7))
        for _ in range(4):   # both workers eventually eat a poison item
            ex.put(7)
        with pytest.raises(WorkerError, match="died"):
            _collect(ex, 4, timeout=60)
    finally:
        ex.stop()
        ex.join()
