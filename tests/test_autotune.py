"""Closed-loop autotune tests: dynamic pool resize correctness under load
and chaos, controller decision semantics (grow/revert/hysteresis) against
canned sampler series, end-to-end convergence observability, and the
autotune-off A/B (zero knob mutations when disabled).

Resize invariants under test (ISSUE 5 acceptance): the exact row multiset
and the ordinal-exact resume cursor survive grow/shrink mid-epoch - even
with hard kills and hangs active - and the resizable-semaphore accounting
returns to baseline after a shrink (no leaked slots).
"""

import queue
import threading
import time

import numpy as np
import pytest

from petastorm_tpu.autotune import (AutotuneController, AutotunePolicy,
                                    resolve_autotune)
from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.pool import (ThreadedExecutor, VentilatedItem,
                                _ResizableSemaphore)
from petastorm_tpu.telemetry import Telemetry
from petastorm_tpu.test_util.stub_workers import SleepyWorker


# -- resizable semaphore ------------------------------------------------------

def test_resizable_semaphore_accounting():
    sem = _ResizableSemaphore(2)
    assert sem.acquire(blocking=False) and sem.acquire(blocking=False)
    assert sem.in_use == 2
    assert not sem.acquire(blocking=False)  # full at bound
    sem.set_bound(3)
    assert sem.acquire(blocking=False)      # growth frees a slot immediately
    for _ in range(3):
        sem.release()
    assert sem.in_use == 0
    with pytest.raises(ValueError):
        sem.release()                        # overdraft guard survives resize


def test_resizable_semaphore_shrink_blocks_until_drained():
    sem = _ResizableSemaphore(3)
    for _ in range(3):
        assert sem.acquire(timeout=1)
    sem.set_bound(1)                         # below current in_use: legal
    assert not sem.acquire(timeout=0.05)     # over the new bound
    sem.release()
    sem.release()                            # in_use 1 == bound: still full
    assert not sem.acquire(timeout=0.05)
    sem.release()                            # in_use 0 < bound 1
    assert sem.acquire(timeout=1)
    sem.release()
    assert sem.in_use == 0


def test_resizable_semaphore_grow_wakes_blocked_waiter():
    sem = _ResizableSemaphore(1)
    assert sem.acquire(timeout=1)
    got = threading.Event()

    def waiter():
        if sem.acquire(timeout=5):
            got.set()

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.05)
    sem.set_bound(2)
    assert got.wait(timeout=2), "grow did not wake the blocked acquirer"
    t.join(timeout=2)


# -- dynamic thread-pool resize ----------------------------------------------

def _drain(ex, n, timeout=60):
    out = []
    deadline = time.monotonic() + timeout
    while len(out) < n:
        assert time.monotonic() < deadline, f"timed out {len(out)}/{n}"
        try:
            out.append(ex.get(timeout=0.5))
        except queue.Empty:
            continue
    return out


def test_thread_pool_resize_under_load_exact_multiset():
    """Grow 2 -> 8 -> shrink to 1 while 300 items stream through: every item
    delivered exactly once, semaphore accounting back to baseline, retired
    slots actually gone (acceptance: 8-thread resize-under-load stress)."""
    n = 300
    ex = ThreadedExecutor(workers_count=2, results_queue_size=8)
    with ex:
        ex.start(SleepyWorker(0.002))
        stop_feeding = threading.Event()

        def feed():
            for i in range(n):
                if stop_feeding.is_set():
                    return
                ex.put(VentilatedItem(i, i))

        feeder = threading.Thread(target=feed, daemon=True)
        feeder.start()
        out = []
        out.extend(v.item for v in _drain(ex, 40))
        assert ex.resize_workers(8) == 8
        out.extend(v.item for v in _drain(ex, 120))
        assert ex.resize_workers(1) == 1
        out.extend(v.item for v in _drain(ex, n - len(out)))
        feeder.join(timeout=10)
        assert sorted(out) == list(range(n))  # exact multiset, no dup/loss
        # no leaked slots: every queue slot acquired was released
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and ex._out_slots.in_use:
            time.sleep(0.02)
        assert ex._in_slots.in_use == 0
        assert ex._out_slots.in_use == 0
        diag = ex.diagnostics
        assert diag["workers_count"] == 1
        # 8 were live at peak; shrinking to 1 retires 7 at item boundaries
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and ex.diagnostics["workers_retired"] < 7):
            time.sleep(0.02)
        assert ex.diagnostics["workers_retired"] == 7
        # the default input bound tracks workers + 2 across resizes
        assert diag["in_queue_bound"] == 3
        stop_feeding.set()


def test_thread_pool_results_bound_resize_live():
    ex = ThreadedExecutor(workers_count=1, results_queue_size=1)
    with ex:
        ex.start(SleepyWorker(0))
        for i in range(3):
            ex.put(VentilatedItem(i, i))
        time.sleep(0.3)  # worker now blocked on the 1-deep results bound
        assert ex.set_results_bound(8) == 8
        got = sorted(v.item for v in _drain(ex, 3))
        assert got == [0, 1, 2]
        assert ex.diagnostics["results_queue_bound"] == 8


def test_thread_pool_prestart_resize_tracks_input_bound():
    """resize_workers before start() must carry the default workers+2
    input bound along with the target, not leave it sized for the
    construction-time count (8 workers against a 5-slot input queue would
    idle three of them)."""
    ex = ThreadedExecutor(workers_count=3)
    assert ex.resize_workers(8) == 8
    assert ex._in_slots.bound == 10
    # an explicit in_queue_size is the caller's choice - left alone
    ex2 = ThreadedExecutor(workers_count=3, in_queue_size=4)
    ex2.resize_workers(8)
    assert ex2._in_slots.bound == 4


def test_thread_pool_grow_reuses_retired_slots():
    """Perpetual shrink/grow probes (autotune's explore mode runs for the
    life of the reader) must not grow _threads/_worker_state without bound:
    grow respawns into cleanly-retired slots, like the process pool
    (review finding)."""
    ex = ThreadedExecutor(workers_count=4, results_queue_size=8)
    with ex:
        ex.start(SleepyWorker(0))
        for _ in range(5):
            ex.resize_workers(2)
            deadline = time.monotonic() + 10
            # wait for the flagged slots to exit so reuse is deterministic
            while time.monotonic() < deadline and (
                    ex.diagnostics["workers_retired"] < 2
                    or any(ex._threads[i].is_alive() for i in ex._retired)):
                time.sleep(0.01)
            assert ex.diagnostics["workers_retired"] == 2
            ex.resize_workers(4)
        assert len(ex._threads) == 4     # every grow reused retired slots
        assert len(ex._worker_state) == 4
        with ex._resize_lock:
            assert len(ex._active_slots()) == 4
        # the reused plane still works (feed from a thread: 20 items exceed
        # the in+results+in-worker capacity, so an inline feed would wedge
        # against the backpressure bounds before _drain ever runs)
        def feed():
            for i in range(20):
                ex.put(VentilatedItem(i, i))

        feeder = threading.Thread(target=feed, daemon=True)
        feeder.start()
        assert sorted(v.item for v in _drain(ex, 20)) == list(range(20))
        feeder.join(timeout=10)


def test_recovered_abandoned_slot_trimmed_to_target():
    """A target-managed pool heals in a replacement the moment a hung slot
    is abandoned; a thread cannot be killed, so when the hang later
    resolves the recovered slot must be retired instead of silently
    rejoining the plane at target+1 workers (review finding)."""
    from petastorm_tpu.test_util.stub_workers import BlockingWorker

    release = threading.Event()
    ex = ThreadedExecutor(workers_count=2, results_queue_size=8,
                          item_deadline_s=0.4)
    try:
        with ex:
            ex.start(BlockingWorker(release, trigger=1))
            ex.resize_workers(2)         # declare target management
            for i in range(6):
                ex.put(VentilatedItem(i, i))
            out = [v.item for v in _drain(ex, 5)]   # item 1 is wedged
            # poll to drive the deadline sweep: the hung slot is abandoned
            # and a replacement healed in
            deadline = time.monotonic() + 10
            while (time.monotonic() < deadline
                   and not ex.diagnostics["hung_workers_abandoned"]):
                try:
                    out.append(ex.get(timeout=0.05).item)
                except queue.Empty:
                    pass
            assert ex.diagnostics["hung_workers_abandoned"] == 1
            release.set()                # the hang resolves
            out.extend(v.item for v in _drain(ex, 6 - len(out)))
            assert sorted(out) == list(range(6))    # exactly-once held
            # keep sweeping: the recovered slot is trimmed back to target
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                try:
                    ex.get(timeout=0.05)
                except queue.Empty:
                    pass
                with ex._resize_lock:
                    active = len(ex._active_slots())
                if not ex._abandoned and active <= 2:
                    break
            assert not ex._abandoned
            assert active == 2           # not target+1: overshoot trimmed
    finally:
        release.set()                    # never leave the worker wedged


def test_reader_resize_under_chaos_exact_rows(tmp_path):
    """Thread-pool resize mid-epoch with a hard kill AND a permanent hang
    active (deadline recovery) keeps the row multiset and the ordinal-exact
    cursor intact."""
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.test_util.chaos import ChaosSpec

    url = str(tmp_path / "ds")
    schema = Schema("S", [Field("x", np.int64)])
    write_dataset(url, schema, [{"x": i} for i in range(240)],
                  row_group_size_rows=4)
    chaos = ChaosSpec(kill_ordinals=(5,), hang_ordinals=(11,), hang_s=600)
    with make_batch_reader(url, reader_pool_type="thread", workers_count=2,
                           shuffle_row_groups=False, chaos=chaos,
                           item_deadline_s=1.0) as r:
        rows = []
        resized = []
        for i, b in enumerate(r.iter_batches()):
            rows.extend(int(v) for v in b.columns["x"])
            if i == 5:
                resized.append(r._executor.resize_workers(6))
            elif i == 25:
                resized.append(r._executor.resize_workers(1))
        state = r.state_dict()
        diag = r.diagnostics
    assert resized == [6, 1]
    assert sorted(rows) == list(range(240))
    assert state["ordinal_exact"] and state["position"] == 60
    assert diag["requeued_items"] >= 2  # the kill and the hang both recovered


@pytest.mark.slow
def test_process_pool_resize_under_chaos_exact_rows(tmp_path):
    """Process-pool grow (spawn into spare slots) + shrink (retire flag, exit
    at item boundary) under a hard kill: exact multiset, exact cursor, no
    slot leaks (acceptance: process-pool resize-under-load stress)."""
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.test_util.chaos import ChaosSpec

    url = str(tmp_path / "ds")
    schema = Schema("S", [Field("x", np.int64)])
    write_dataset(url, schema, [{"x": i} for i in range(120)],
                  row_group_size_rows=4)
    chaos = ChaosSpec(kill_ordinals=(6,))
    with make_batch_reader(url, reader_pool_type="process", workers_count=2,
                           shuffle_row_groups=False, chaos=chaos) as r:
        rows = []
        resized = []
        for i, b in enumerate(r.iter_batches()):
            rows.extend(int(v) for v in b.columns["x"])
            if i == 3:
                resized.append(r._executor.resize_workers(3))
            elif i == 15:
                resized.append(r._executor.resize_workers(1))
        state = r.state_dict()
        # retirement is acked at the worker's next item boundary - give the
        # flagged workers a beat to reach it before reading the ledger
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and r.diagnostics["workers_retired"] < 1):
            time.sleep(0.05)
        diag = r.diagnostics
    assert resized == [3, 1]
    assert sorted(rows) == list(range(120))
    assert state["ordinal_exact"] and state["position"] == 30
    assert diag["requeued_items"] >= 1
    assert diag["workers_retired"] >= 1


def test_process_pool_resize_clamps_to_slot_capacity():
    from petastorm_tpu.pool import _ProcessExecutor

    ex = _ProcessExecutor(workers_count=2, max_workers=4)
    assert ex.max_resize_workers == 4
    # unstarted: resize just records the clamped target
    assert ex.resize_workers(16) == 4
    assert ex.resize_workers(0) == 1


def test_process_pool_full_wait_signal_crosses_boundary():
    """A worker blocked on a full results channel accumulates its wait in a
    shared per-slot cell that the parent folds into
    ``queue.results_full_wait_s`` - the consumer-bound signal the controller
    shrinks on must work for process pools even though the blocking happens
    in a child process."""
    from petastorm_tpu.pool import VentilatedItem, _ProcessExecutor
    from petastorm_tpu.test_util.stub_workers import SleepyWorker

    tele = Telemetry()
    with _ProcessExecutor(workers_count=1, results_queue_size=1,
                          telemetry=tele) as ex:
        ex.start(SleepyWorker(0.0))
        for i in range(4):
            ex.put(VentilatedItem(i, i))
        # the worker delivers item 0 into the only slot, then blocks inside
        # put() on item 1 until the consumer drains - let it accrue wait
        time.sleep(1.2)
        got = sorted(ex.get(timeout=30).item for _ in range(4))
    assert got == [0, 1, 2, 3]
    waited = tele.snapshot()["counters"].get("queue.results_full_wait_s", 0.0)
    assert waited > 0.5, waited


# -- controller decision semantics (canned series, fake clock) ---------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class FakeSampler:
    def __init__(self):
        self.points = []

    def series(self):
        return list(self.points)

    def __len__(self):
        return len(self.points)


def _point(rate, starved=0.0, blocked=0.0, dt=1.0):
    return {"dt_s": dt,
            "rates": {"reader.rows_emitted": rate,
                      "queue.results_empty_wait_s": starved,
                      "queue.results_full_wait_s": blocked},
            "gauges": {}, "counters": {}, "stages": {}}


def _controller(workers=2, results_queue_size=50, **policy_kwargs):
    policy_kwargs.setdefault("settle_s", 1.0)
    policy_kwargs.setdefault("eval_points", 2)
    policy_kwargs.setdefault("cooldown_s", 0.0)
    tele = Telemetry()
    sampler = FakeSampler()
    ex = ThreadedExecutor(workers_count=workers,  # unstarted: resize = target
                          results_queue_size=results_queue_size)
    clock = FakeClock()
    ctl = AutotuneController(ex, sampler, tele,
                             policy=AutotunePolicy(**policy_kwargs),
                             clock=clock)
    return ctl, ex, sampler, clock, tele


def _resolve_move(ctl, sampler, clock, after_points):
    """Walk a pending move through settle + evaluation with canned points."""
    clock.t += ctl.policy.settle_s + 0.01
    assert ctl.step() is None            # settle over: eval window opens
    sampler.points.extend(after_points)
    return ctl.step()


def test_controller_grows_workers_when_starved():
    ctl, ex, sampler, clock, tele = _controller(workers=2)
    sampler.points.extend([_point(100, starved=0.9)] * 2)
    entry = ctl.step()
    assert entry is not None
    assert (entry["knob"], entry["action"]) == ("workers", "grow")
    assert ex._workers_count == 3
    done = _resolve_move(ctl, sampler, clock, [_point(150)] * 2)
    assert done["outcome"] == "kept"
    assert ex._workers_count == 3
    counters = tele.snapshot()["counters"]
    assert counters["autotune.moves_applied"] == 1
    assert counters["autotune.moves_kept"] == 1
    assert tele.snapshot()["gauges"]["autotune.workers"] == 3


def test_controller_reverts_regression_and_blocks_direction():
    ctl, ex, sampler, clock, tele = _controller(workers=2)
    sampler.points.extend([_point(100, starved=0.9)] * 2)
    assert ctl.step()["to"] == 3
    done = _resolve_move(ctl, sampler, clock, [_point(60)] * 2)  # -40%
    assert done["outcome"] == "reverted"
    assert ex._workers_count == 2        # knob restored
    # hysteresis: the reverted (workers, grow) direction is blocked, so the
    # same starved signal now falls through to the next candidate knob
    clock.t += 10
    sampler.points.extend([_point(100, starved=0.9)] * 2)
    entry = ctl.step()
    assert entry["knob"] == "results_queue" and entry["action"] == "grow"
    assert tele.snapshot()["counters"]["autotune.moves_reverted"] == 1


def test_controller_consumer_bound_shrinks_workers():
    ctl, ex, sampler, clock, _tele = _controller(workers=4)
    sampler.points.extend([_point(100, blocked=0.8)] * 2)
    entry = ctl.step()
    assert (entry["knob"], entry["action"]) == ("workers", "shrink")
    assert ex._workers_count == 3


def test_controller_exploration_probe_when_no_signal():
    ctl, ex, sampler, clock, _tele = _controller(workers=4)
    sampler.points.extend([_point(100)] * 2)   # no queue-wait signal at all
    entry = ctl.step()
    assert entry["reason"] == "exploration probe"
    assert entry["knob"] == "workers" and entry["to"] == 3
    # explore=False policies sit still instead
    ctl2, ex2, sampler2, _clock2, _tele2 = _controller(workers=4,
                                                       explore=False)
    sampler2.points.extend([_point(100)] * 2)
    assert ctl2.step() is None
    assert ex2._workers_count == 4


def test_controller_respects_bounds():
    ctl, ex, sampler, clock, _tele = _controller(workers=1, max_workers=1,
                                                 min_results_queue=2,
                                                 max_results_queue=2,
                                                 results_queue_size=2)
    sampler.points.extend([_point(100, starved=0.9)] * 2)
    assert ctl.step() is None            # every candidate already at bound


def test_controller_ignores_unbounded_results_queue():
    """results_queue_size <= 0 is documented as unbounded (a 2**30-slot
    semaphore); tuning it would CLAMP it to max_results_queue, so a 'grow'
    would actually collapse the queue to 128 deep.  The controller must
    leave such queues alone."""
    ctl, ex, sampler, clock, _tele = _controller(results_queue_size=0)
    assert "results_queue" not in ctl.knobs()
    assert ex._out_slots.bound == 2 ** 30
    # a consumer-bound signal can no longer reach for the absent knob
    sampler.points.extend([_point(100, blocked=0.9)] * 2)
    entry = ctl.step()
    assert entry is None or entry["knob"] != "results_queue"
    assert ex._out_slots.bound == 2 ** 30


def test_controller_evaluates_pending_on_full_sampler_ring():
    """The sampler ring is a bounded deque: once full, len() pins at maxlen
    forever, so length-based freshness slicing would never see a new point
    and any pending move would stay unresolved for the rest of the run.
    Freshness is anchored by point identity instead (review finding)."""
    import collections

    ctl, ex, sampler, clock, _tele = _controller(workers=2)
    sampler.points = collections.deque(
        [_point(100, starved=0.9) for _ in range(4)], maxlen=4)
    entry = ctl.step()
    assert entry is not None and entry["outcome"] == "pending"
    clock.t += ctl.policy.settle_s + 0.01
    assert ctl.step() is None                # anchors the eval window
    sampler.points.extend(_point(150) for _ in range(2))
    assert len(sampler.points) == 4          # ring rolled; len unchanged
    done = ctl.step()
    assert done is not None and done["outcome"] == "kept"
    assert ex._workers_count == 3
    # anchor aged fully out of the ring: every buffered point counts fresh
    sampler.points.extend([_point(100, starved=0.9) for _ in range(4)])
    entry = ctl.step()
    assert entry is not None and entry["outcome"] == "pending"
    clock.t += ctl.policy.settle_s + 0.01
    assert ctl.step() is None
    sampler.points.extend(_point(160) for _ in range(4))  # evicts the anchor
    done = ctl.step()
    assert done is not None and done["outcome"] == "kept"


def test_controller_unwedges_after_all_directions_blocked():
    """Hysteresis blocks previously aged only when a decision RESOLVED; with
    every (knob, direction) blocked no move can start, so nothing resolved
    and the controller wedged permanently inert.  A no-move decision
    opportunity must age the blocks too (review finding)."""
    ctl, ex, sampler, clock, _tele = _controller(workers=2, block_rounds=2)
    for name in ctl._knobs:
        for direction in (+1, -1):
            ctl._blocked[(name, direction)] = 2
    sampler.points.extend([_point(100, starved=0.9) for _ in range(2)])
    assert ctl.step() is None                # blocked round: ages 2 -> 1
    clock.t = ctl._cooldown_until + 0.01
    assert ctl.step() is None                # blocked round: ages 1 -> gone
    clock.t = ctl._cooldown_until + 0.01
    entry = ctl.step()                       # willing to move again
    assert entry is not None
    assert (entry["knob"], entry["action"]) == ("workers", "grow")


def test_resolve_autotune_modes():
    assert resolve_autotune(None, 4, "thread") is None
    assert isinstance(resolve_autotune(True, 4, "thread"), AutotunePolicy)
    assert isinstance(resolve_autotune(None, "auto", "thread"),
                      AutotunePolicy)
    assert resolve_autotune(False, "auto", "thread") is None
    policy = AutotunePolicy(max_workers=4)
    assert resolve_autotune(policy, 4, "thread") is policy
    with pytest.raises(PetastormTpuError):
        resolve_autotune("yes", 4, "thread")


def test_resolve_autotune_serial_refused_with_warning(caplog):
    import logging

    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.autotune"):
        assert resolve_autotune(True, 4, "serial") is None
    assert any("serial" in rec.message for rec in caplog.records)


# -- end-to-end: autotuned read, observability, off-A/B -----------------------

def _write_slow_ds(tmp_path, rows=400, rg=4):
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.schema import Field, Schema

    url = str(tmp_path / "ds")
    schema = Schema("S", [Field("x", np.int64)])
    write_dataset(url, schema, [{"x": i} for i in range(rows)],
                  row_group_size_rows=rg)
    return url


def _sleep_transform():
    from petastorm_tpu.transform import TransformSpec

    def slow(cols):
        time.sleep(0.01)
        return cols

    return TransformSpec(slow)


def test_reader_autotune_e2e_decisions_and_observability(tmp_path):
    """An autotuned read from bad knobs (workers=1) must converge upward,
    deliver the exact rows, and leave every decision observable: counters in
    the Prometheus exposition, the knob-trajectory gauges in the sampled
    series (what a flight record carries), and the decision log in
    diagnostics (ISSUE 5 acceptance)."""
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.telemetry.export import render_prometheus
    from petastorm_tpu.telemetry.sampler import flight_record

    url = _write_slow_ds(tmp_path)
    tele = Telemetry()
    policy = AutotunePolicy(warmup_s=0.2, settle_s=0.2, tick_s=0.05,
                            eval_points=2, cooldown_s=0.1)
    with make_batch_reader(url, reader_pool_type="thread", workers_count=1,
                           shuffle_row_groups=False, num_epochs=2,
                           transform_spec=_sleep_transform(),
                           telemetry=tele, autotune=policy,
                           sample_interval_s=0.1) as r:
        assert r.autotune is not None
        rows = sorted(int(v) for b in r.iter_batches()
                      for v in b.columns["x"])
        record = flight_record(r.sampler, reason="test")
        diag = r.diagnostics
    assert rows == sorted(list(range(400)) * 2)
    at = diag["autotune"]
    assert at["moves_applied"] >= 1
    assert at["decisions"] and at["decisions"][0]["knob"]
    assert at["knobs"]["workers"] >= 2  # grew off the bad seed
    counters = tele.snapshot()["counters"]
    assert counters["autotune.moves_applied"] == at["moves_applied"]
    # knob trajectory rides the sampled series -> flight records show it
    assert any("autotune.workers" in p.get("gauges", {})
               for p in record["points"])
    exposition = render_prometheus(tele.snapshot())
    assert "petastorm_tpu_autotune_moves_applied_total" in exposition
    # trace tail carries the per-move events
    assert any(e.get("cat") == "autotune" for e in tele.trace.tail(500))


def test_autotune_off_zero_knob_mutations(tmp_path):
    """The disabled path is untouched: no controller, no autotune counters,
    static knobs - the A/B half of the no-overhead-when-off contract."""
    from petastorm_tpu.reader import make_batch_reader

    url = _write_slow_ds(tmp_path, rows=120)
    tele = Telemetry()
    with make_batch_reader(url, reader_pool_type="thread", workers_count=2,
                           shuffle_row_groups=False, telemetry=tele) as r:
        assert r.autotune is None
        rows = sorted(int(v) for b in r.iter_batches()
                      for v in b.columns["x"])
        diag = r.diagnostics
    assert rows == list(range(120))
    assert "autotune" not in diag
    assert diag["workers_count"] == 2
    assert diag["results_queue_bound"] == 10  # the construction-time default
    assert not any(n.startswith("autotune.")
                   for n in tele.snapshot()["counters"])


def test_workers_count_auto_arms_runtime_loop(tmp_path):
    """'auto' now seeds from the core heuristic AND runs the tuner;
    autotune=False restores the static-only behavior."""
    from petastorm_tpu.reader import make_batch_reader

    url = _write_slow_ds(tmp_path, rows=16, rg=8)
    with make_batch_reader(url, workers_count="auto", num_epochs=1) as r:
        assert r.autotune is not None
        list(r.iter_batches())
    with make_batch_reader(url, workers_count="auto", num_epochs=1,
                           autotune=False) as r:
        assert r.autotune is None
        list(r.iter_batches())


def test_serial_stall_abort_warns_at_construction(tmp_path, caplog):
    """ADVICE r5: the reader-side stall loop can never observe a serial-pool
    mid-item wedge, so combining stall_abort_s with the serial pool warns
    loudly at construction instead of silently never firing."""
    import logging

    from petastorm_tpu.reader import make_batch_reader

    url = _write_slow_ds(tmp_path, rows=16, rg=8)
    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.reader"):
        with make_batch_reader(url, reader_pool_type="serial",
                               stall_abort_s=30) as r:
            list(r.iter_batches())
    assert any("inoperative" in rec.message and "serial" in rec.message
               for rec in caplog.records)


def test_reader_join_bounded_typeerror_propagates(tmp_path):
    """ADVICE r5 regression guard: a TypeError raised INSIDE a bounded
    executor join must propagate (the capability gate is inspect.signature,
    not exception catching - a silent unbounded re-join would reintroduce
    the close hang the abort path exists to prevent)."""
    from petastorm_tpu.reader import make_batch_reader

    url = _write_slow_ds(tmp_path, rows=16, rg=8)
    reader = make_batch_reader(url, reader_pool_type="thread",
                               workers_count=1)
    list(reader.iter_batches())
    reader.stop()
    reader._stall_aborted = True

    def exploding_join(timeout=None):
        raise TypeError("raised inside a bounded join")

    reader._executor.join = exploding_join
    with pytest.raises(TypeError, match="inside a bounded join"):
        reader.join()


def test_loader_prefetch_knob_attaches_and_resizes(tmp_path):
    """A JaxDataLoader over an autotuned reader registers its prefetch depth
    as a knob; set_prefetch resizes both producer queues live."""
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    url = _write_slow_ds(tmp_path, rows=64, rg=8)
    policy = AutotunePolicy(warmup_s=60)  # armed but quiescent for this test
    with make_batch_reader(url, reader_pool_type="thread", workers_count=1,
                           shuffle_row_groups=False, num_epochs=1,
                           autotune=policy) as r:
        with JaxDataLoader(r, batch_size=8, prefetch=2,
                           mesh=None) as loader:
            assert "prefetch" in r.autotune.knobs()
            assert loader.prefetch == 2
            assert loader.set_prefetch(5) == 5
            assert loader.prefetch == 5
            assert r.autotune.knobs()["prefetch"] == 5
            n = sum(int(next(iter(b.values())).shape[0]) for b in loader)
    assert n == 64
