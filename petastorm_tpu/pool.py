"""Executor pools: the host-side concurrency plane feeding the device pipeline.

Reference parity: petastorm/workers_pool/ (~1,100 LoC) - WorkerBase protocol
(worker_base.py:18-35), ThreadPool with bounded results queue + stop-aware puts +
exception forwarding (thread_pool.py:78-221), zmq-based ProcessPool with spawned
workers, startup barrier, orphan watchdog and slow-joiner workarounds
(process_pool.py:114-428), DummyPool doing work inside get_results
(dummy_pool.py:20-91), and ConcurrentVentilator with bounded in-flight and per-epoch
reshuffle (ventilator.py:55-166).

Design differences (TPU-first):

* **Threads are the default.** pyarrow parquet IO and decode release the GIL, so the
  reference's zmq process plumbing is usually pure overhead on a TPU host VM;
  ``ProcessExecutor`` (multiprocessing.spawn, no zmq) remains for GIL-bound python
  transforms.  Spawn (not fork) for the same reason the reference documents
  (process_pool.py:15-17: forked JVM/arrow handles break).
* **Completion-order results with explicit epoch accounting.** The consumer knows
  exactly how many items each epoch ventilates (ReadPlan is deterministic), so
  epoch-end is a counted event, not a sentinel race.
* Worker exceptions carry the formatted remote traceback and re-raise at the
  consumer (reference thread_pool.py:68-73,169-172).
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
import traceback
from abc import ABC, abstractmethod
from typing import Any, Callable, Iterable, Optional

from petastorm_tpu.errors import (DEFAULT_REQUEUE_ATTEMPTS,
                                  PetastormTpuError, ReaderClosedError,
                                  classify_error)
from petastorm_tpu.telemetry import resolve as _resolve_telemetry

logger = logging.getLogger(__name__)

_POLL_S = 0.05
DEFAULT_RESULTS_QUEUE_SIZE = 50  # reference: reader.py:61
_MISSING = object()


def _env_seconds(name: str, default: float) -> float:
    """Float env var with a logged fallback (shared with reader.py)."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("Ignoring non-numeric %s=%r (using %.0f)",
                       name, raw, default)
        return default


class WorkerError(PetastormTpuError):
    """A worker failed; message includes the remote traceback (when the
    worker lived long enough to produce one).

    Carries the failure-classification metadata the reader's ``on_error``
    policy dispatches on: ``kind`` (``'data'`` = property of the work item,
    skip-eligible; ``'infra'`` = property of the worker, requeue-eligible),
    and - when the failure is attributable to a single work item -
    ``ordinal``, ``item`` and ``exc_type``.  Unattributable failures
    (all workers died, stall abort) keep the defaults and are never
    skippable.
    """

    def __init__(self, message: str, kind: str = "infra", ordinal=None,
                 item=None, exc_type: Optional[str] = None):
        super().__init__(message)
        self.kind = kind
        self.ordinal = ordinal
        self.item = item
        self.exc_type = exc_type


class PipelineStallError(WorkerError):
    """The reader produced no result for ``stall_abort_s`` seconds and
    aborted (``make_reader(stall_abort_s=...)`` /
    ``PETASTORM_TPU_STALL_ABORT_S``).

    Subclasses :class:`WorkerError` (kind ``'infra'``, unattributable - no
    single work item to blame) so existing handlers keep working; carries
    the full pipeline ``diagnostics`` snapshot taken at abort time, so the
    wedged state (stuck workers, queue depths, in-flight items) survives
    into the traceback instead of living only in scrolled-away warnings.
    """

    def __init__(self, message: str, diagnostics: Optional[dict] = None):
        super().__init__(message, kind="infra")
        self.diagnostics = diagnostics or {}


class VentilationCancelled(Exception):
    """An ``executor.put`` blocked on a full queue was withdrawn by its
    cancel_event (Ventilator.pause_and_join with a saturated pipeline); the
    item was NOT enqueued.  Internal control flow, never user-visible."""


class _ResizableSemaphore:
    """Counting semaphore whose bound can change while waiters are blocked.

    The executors' queue bounds live in semaphores (see ThreadedExecutor's
    queue-choice comment); runtime autotuning (petastorm_tpu.autotune) needs
    those bounds adjustable mid-flight.  ``threading.BoundedSemaphore`` bakes
    its bound in at construction, so this replaces it with the same acquire/
    release contract plus ``set_bound``:

    * accounting stays EXACT across a resize: ``in_use`` only moves via
      acquire/release, so every acquired slot must still be released and a
      release without a matching acquire still raises (the BoundedSemaphore
      overdraft guard the pools rely on to catch accounting bugs);
    * shrinking below the current ``in_use`` never strands or cancels held
      slots - new acquires simply block until releases bring ``in_use``
      under the new bound;
    * growing wakes every blocked waiter so freed capacity is used at once.
    """

    __slots__ = ("_bound", "_in_use", "_cond")

    def __init__(self, bound: int):
        if bound < 1:
            raise PetastormTpuError(f"semaphore bound must be >= 1, got {bound}")
        self._bound = int(bound)
        self._in_use = 0
        self._cond = threading.Condition()

    def acquire(self, blocking: bool = True,
                timeout: Optional[float] = None) -> bool:
        with self._cond:
            if not blocking:
                if self._in_use < self._bound:
                    self._in_use += 1
                    return True
                return False
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            while self._in_use >= self._bound:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    return False
                self._cond.wait(remaining)
            self._in_use += 1
            return True

    def release(self) -> None:
        with self._cond:
            if self._in_use <= 0:
                raise ValueError("semaphore released more times than acquired")
            self._in_use -= 1
            self._cond.notify()

    def set_bound(self, bound: int) -> None:
        """Change the bound; growth wakes all blocked acquirers."""
        if bound < 1:
            raise PetastormTpuError(f"semaphore bound must be >= 1, got {bound}")
        with self._cond:
            self._bound = int(bound)
            self._cond.notify_all()

    @property
    def bound(self) -> int:
        return self._bound

    @property
    def in_use(self) -> int:
        """Slots currently held (== bound means the queue is full)."""
        return self._in_use


class _Failure:
    """A worker exception crossing back to the consumer (picklable)."""

    __slots__ = ("formatted", "kind", "exc_type", "ordinal", "item")

    def __init__(self, exc: BaseException, ordinal=None, item=None):
        self.formatted = "".join(traceback.format_exception(type(exc), exc, exc.__traceback__))
        self.kind = classify_error(exc)
        self.exc_type = type(exc).__name__
        self.ordinal = ordinal
        self.item = item


class _Ok:
    """Success envelope tagging a result with its work-item ordinal, so the
    consumer side can settle the in-flight ledger (requeue dedup: a result
    for an ordinal no longer in flight is a duplicate and is dropped).

    ``attempt`` is the delivering item's attempt number: it lets the
    consumer attribute a hedged ordinal's first delivery to the hedge copy
    vs the original (``liveness.hedge_wins``)."""

    __slots__ = ("ordinal", "value", "attempt")

    def __init__(self, ordinal, value, attempt: int = 0):
        self.ordinal = ordinal
        self.value = value
        self.attempt = attempt

    def __getstate__(self):
        return (self.ordinal, self.value, self.attempt)

    def __setstate__(self, state):
        self.ordinal, self.value = state[0], state[1]
        self.attempt = state[2] if len(state) > 2 else 0


def _worker_error(exc: BaseException, kind: str, ordinal, item) -> WorkerError:
    """One classified WorkerError from a live exception (single place that
    encodes the message/metadata shape, shared with the _Failure path)."""
    failure = _Failure(exc, ordinal=ordinal, item=item)
    return WorkerError(f"Worker failed:\n{failure.formatted}", kind=kind,
                       ordinal=ordinal, item=item, exc_type=failure.exc_type)


#: worker factory: () -> process_fn(item) -> result.  Must be picklable for
#: ProcessExecutor (a module-level class instance holding plain-data config).
WorkerFactory = Callable[[], Callable[[Any], Any]]


class VentilatedItem:
    """A work item tagged with its absolute ventilation ordinal.

    Pools may complete items out of ventilation order; the ordinal lets the
    consumer reconstruct the exact contiguous consumed prefix (the only
    resume cursor that can guarantee no item is ever lost).  Picklable for
    the process pool.

    ``attempt`` counts infra-failure requeues of this ordinal (0 = first
    delivery); it rides the item itself so deterministic fault injection
    (test_util.chaos) can key on it across process boundaries.
    """

    __slots__ = ("ordinal", "item", "attempt")

    def __init__(self, ordinal: int, item: Any, attempt: int = 0):
        self.ordinal = ordinal
        self.item = item
        self.attempt = attempt

    def __getstate__(self):
        return (self.ordinal, self.item, self.attempt)

    def __setstate__(self, state):
        self.ordinal, self.item = state[0], state[1]
        self.attempt = state[2] if len(state) > 2 else 0


class ExecutorBase(ABC):
    """start -> (put*/get*) -> stop -> join lifecycle, mirroring the reference pool
    protocol (start/ventilate/get_results/stop/join).

    Failure handling (docs/operations.md "Failure handling"): work items
    carrying a ventilation ordinal are tracked in an in-flight ledger from
    ``put`` until their result (or attributed failure) is delivered at
    ``get``.  When a worker dies mid-item (process crash/OOM, or a simulated
    crash in tests), the ledger + worker heartbeat identify the lost item and
    it is requeued onto surviving workers up to ``max_requeue_attempts``
    times; the ledger also dedups the rare double delivery (worker died
    after queueing its result but before clearing its heartbeat).
    ``stop_on_failure=False`` (the reader's ``on_error`` skip policies) keeps
    the pool running when a failure is delivered, so the consumer can skip
    the item and keep iterating.
    """

    def __init__(self, telemetry=None, stop_on_failure: bool = True,
                 max_requeue_attempts: int = DEFAULT_REQUEUE_ATTEMPTS,
                 item_deadline_s: Optional[float] = None,
                 hedge_after_s=None):
        self._stopped = False
        self._ventilated = 0
        self._consumed = 0
        self._stop_on_failure = stop_on_failure
        self._max_requeue = max_requeue_attempts
        if item_deadline_s is not None and item_deadline_s <= 0:
            raise PetastormTpuError("item_deadline_s must be > 0 or None")
        if not (hedge_after_s is None or hedge_after_s == "auto"
                or (isinstance(hedge_after_s, (int, float))
                    and hedge_after_s > 0)):
            raise PetastormTpuError(
                "hedge_after_s must be a positive number, 'auto', or None;"
                f" got {hedge_after_s!r}")
        #: liveness knobs (docs/operations.md "Liveness & stragglers"):
        #: an in-flight item older than item_deadline_s gets its worker
        #: killed (process pool) or its slot abandoned (thread pool) and is
        #: requeued; one older than hedge_after_s is speculatively re-issued
        #: to an idle worker, first result wins
        self._item_deadline_s = item_deadline_s
        self._hedge_after = hedge_after_s
        #: ordinal -> latest in-flight VentilatedItem (items without an
        #: ordinal are not tracked: they cannot be requeued or deduped)
        self._inflight: dict = {}
        self._inflight_lock = threading.Lock()
        self._requeued_items = 0
        #: requeued items waiting for an input-queue slot (consumer-thread
        #: state: parked by _reinject, drained by _flush_pending_requeues)
        self._pending_requeue: list = []
        #: liveness ledger (consumer-thread state, like _pending_requeue):
        #: ordinal -> attempt number of its hedge copy, until first delivery
        self._hedged_attempt: dict = {}
        self._hung_workers_killed = 0
        self._hung_workers_abandoned = 0
        self._hedged_items = 0
        self._hedge_wins = 0
        #: petastorm_tpu.telemetry recorder (no-op unless enabled); executors
        #: record queue-full wait time - the signal that tells the pipeline
        #: report whether backpressure points upstream or downstream
        self._telemetry = _resolve_telemetry(telemetry)
        self._m_input_full = self._telemetry.counter("queue.input_full_wait_s")
        self._m_results_full = self._telemetry.counter(
            "queue.results_full_wait_s")
        # queue-depth gauges: stamped at put/get so the metrics sampler's
        # 1 s frames carry a live depth curve (the signal a flight record
        # needs to show "the queue drained, then the stall began")
        self._g_in_depth = self._telemetry.gauge("pool.in_queue_depth")
        self._g_out_depth = self._telemetry.gauge("pool.results_queue_depth")
        self._m_requeued = self._telemetry.counter("errors.requeued_items")
        self._m_hung_killed = self._telemetry.counter(
            "liveness.hung_workers_killed")
        self._m_hung_abandoned = self._telemetry.counter(
            "liveness.hung_workers_abandoned")
        self._m_hedged = self._telemetry.counter("liveness.hedged_items")
        self._m_hedge_wins = self._telemetry.counter("liveness.hedge_wins")

    # -- in-flight ledger (requeue + duplicate suppression) -------------------

    def _track_put(self, item: Any) -> None:
        ordinal = getattr(item, "ordinal", None)
        if ordinal is not None:
            with self._inflight_lock:
                self._inflight[ordinal] = item

    def _settle(self, ordinal) -> bool:
        """Remove ``ordinal`` from the in-flight ledger; False = the ordinal
        was already settled (this delivery is a requeue duplicate)."""
        if ordinal is None:
            return True
        with self._inflight_lock:
            return self._inflight.pop(ordinal, _MISSING) is not _MISSING

    def _try_requeue(self, ordinal, why: str) -> bool:
        """Re-ventilate the in-flight item for ``ordinal`` with its attempt
        count bumped; False when the ordinal is untracked (already
        delivered, or never had an ordinal) or its attempt budget is spent."""
        if ordinal is None:
            return False
        with self._inflight_lock:
            item = self._inflight.get(ordinal)
        if item is None:
            return False
        attempt = getattr(item, "attempt", 0)
        if attempt >= self._max_requeue:
            return False
        retry = VentilatedItem(ordinal, getattr(item, "item", item),
                               attempt + 1)
        with self._inflight_lock:
            self._inflight[ordinal] = retry
        # a crash-requeue supersedes any outstanding hedge of this ordinal:
        # the requeued copy's attempt number would otherwise satisfy the
        # 'attempt >= hedged_at' win test and overcount hedge_wins
        self._hedged_attempt.pop(ordinal, None)
        self._requeued_items += 1
        self._m_requeued.add(1)
        logger.warning("Requeueing work item %s after %s (attempt %d/%d)",
                       ordinal, why, attempt + 1, self._max_requeue)
        self._reinject(retry)
        return True

    def _deliver_failure(self, failure: "_Failure") -> bool:
        """Handle a delivered worker failure.

        Infra-kind failures with an attributable item (e.g. an in-worker
        MemoryError) get the same treatment as a worker death: the item is
        healthy, the worker wasn't - requeue it, budget permitting, and
        return True so the caller keeps polling.  Everything else settles
        the ledger and raises a classified WorkerError.
        """
        if failure.kind == "infra" and self._try_requeue(
                failure.ordinal,
                f"in-worker infra failure ({failure.exc_type})"):
            return True
        self._hedged_attempt.pop(failure.ordinal, None)
        if failure.ordinal is not None and not self._settle(failure.ordinal):
            # late failure for an ordinal that was already settled (a
            # requeued item's sibling delivery won the race): drop it like
            # a duplicate _Ok - the item already reached the consumer, so
            # aborting (raise mode) or double-counting a skip would both
            # corrupt the epoch accounting
            logger.warning("Dropping duplicate failure for already-delivered"
                           " work item %s (%s)", failure.ordinal,
                           failure.exc_type)
            return True
        if self._stop_on_failure:
            self.stop()
        raise WorkerError(f"Worker failed:\n{failure.formatted}",
                          kind=failure.kind, ordinal=failure.ordinal,
                          item=failure.item, exc_type=failure.exc_type)

    def _requeue_lost(self, ordinal, why: str,
                      exhausted_kind: str = "infra") -> None:
        """A worker died (or hung past its deadline) holding ``ordinal``:
        re-ventilate it onto surviving workers, or surface a WorkerError once
        the attempt budget is spent.

        ``exhausted_kind``: classification of the budget-exhausted error.
        Crash/OOM paths keep ``'infra'``; the item-deadline path passes
        ``'data'`` - an item that hung EVERY worker that touched it is a
        property of the item (a pathological decode, a poisoned slow row),
        and under an ``on_error`` skip policy it should quarantine like any
        other data error instead of killing the epoch.
        """
        if ordinal is None or self._try_requeue(ordinal, why):
            return
        with self._inflight_lock:
            item = self._inflight.pop(ordinal, None)
        if item is None:
            # the result was delivered before the worker died: nothing lost
            return
        self._hedged_attempt.pop(ordinal, None)
        if self._stop_on_failure:
            self.stop()
        raise WorkerError(
            f"Work item {ordinal} lost to {why}; requeue budget exhausted"
            f" ({getattr(item, 'attempt', 0)} requeue(s) of max"
            f" {self._max_requeue})"
            + (" - repeatedly hung item, quarantine-eligible"
               if exhausted_kind == "data" else " - possible crash/OOM"),
            kind=exhausted_kind, ordinal=ordinal, item=item)

    # -- liveness: straggler hedging (docs/operations.md) ---------------------

    def _hedge_threshold(self) -> Optional[float]:
        """Resolved hedge age threshold in seconds, or None (hedging off /
        'auto' lacks data).  ``'auto'`` derives the threshold from the
        observed decode latency tail: 4x the telemetry p99, floored at 0.5s,
        once at least 20 decodes have been recorded - so hedging arms itself
        against what 'slow' actually means on this dataset.  'auto' needs
        telemetry enabled in THIS process (thread/serial pools; process-pool
        workers record decode stages in their own processes)."""
        h = self._hedge_after
        if h is None:
            return None
        if h == "auto":
            if not self._telemetry.enabled:
                return None
            hist = self._telemetry.histogram("stage.decode.latency_s")
            if getattr(hist, "count", 0) < 20:
                return None
            return max(4.0 * hist.quantile(0.99), 0.5)
        return float(h)

    def _hedge(self, ordinal, why: str) -> bool:
        """Speculatively re-issue the in-flight item for ``ordinal`` (attempt
        bumped, non-blocking enqueue); the per-ordinal ledger guarantees
        whichever copy finishes second is dropped as a duplicate.  Bounded by
        the same attempt budget as requeues; False = not hedged (already
        hedged, budget spent, input queue full, or ordinal already
        delivered).  Consumer-thread context."""
        if ordinal is None or ordinal in self._hedged_attempt:
            return False
        with self._inflight_lock:
            item = self._inflight.get(ordinal)
        if item is None:
            return False
        attempt = getattr(item, "attempt", 0)
        if attempt >= self._max_requeue:
            return False
        copy = VentilatedItem(ordinal, getattr(item, "item", item),
                              attempt + 1)
        if not self._try_enqueue(copy):
            return False  # no room; re-evaluated on the next poll
        with self._inflight_lock:
            self._inflight[ordinal] = copy
        self._hedged_attempt[ordinal] = attempt + 1
        self._hedged_items += 1
        self._m_hedged.add(1)
        logger.info("Hedging work item %s after %s (speculative attempt"
                    " %d/%d; first result wins)", ordinal, why, attempt + 1,
                    self._max_requeue)
        return True

    def _note_delivery(self, ordinal, attempt: int) -> None:
        """First delivery for ``ordinal`` settled: when it was hedged, decide
        whether the hedge copy won (its attempt number delivered first)."""
        if not self._hedged_attempt:
            return
        hedged_at = self._hedged_attempt.pop(ordinal, None)
        if hedged_at is not None and attempt >= hedged_at:
            self._hedge_wins += 1
            self._m_hedge_wins.add(1)

    def _reinject(self, item: Any) -> None:
        """Re-enqueue a requeued item without ever blocking the consumer
        thread: parked when the input queue is full, drained on later
        ``_flush_pending_requeues`` calls."""
        if not self._try_enqueue(item):
            self._pending_requeue.append(item)

    def _flush_pending_requeues(self) -> None:
        while (self._pending_requeue
               and self._try_enqueue(self._pending_requeue[0])):
            self._pending_requeue.pop(0)

    def _try_enqueue(self, item: Any) -> bool:
        """Non-blocking input-queue insert (pool-specific); False = full."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support requeueing")

    def inflight_capacity(self) -> Optional[int]:
        """Upper bound on distinct work items simultaneously in flight
        through this executor, or None when unknown.  The reader's
        deterministic reorder stage uses it as a cheap gate before the
        exact :meth:`is_inflight` ledger check that tells a straggling
        ordinal (normal - keep draining) from one in nobody's ledger (a
        transport bug worth a loud warning)."""
        return None

    def is_inflight(self, ordinal) -> bool:
        """True while ``ordinal`` is tracked in the in-flight ledger (a
        result or attributed failure will still arrive for it)."""
        with self._inflight_lock:
            return ordinal in self._inflight

    @abstractmethod
    def start(self, worker_factory: WorkerFactory) -> None:
        ...

    @abstractmethod
    def put(self, item: Any, cancel_event=None) -> None:
        """Enqueue a work item; blocks on a full input queue.  When
        ``cancel_event`` is set while blocked, raises VentilationCancelled
        WITHOUT having enqueued the item (quiesce with a full pipeline)."""
        ...

    @abstractmethod
    def get(self, timeout: Optional[float] = None) -> Any:
        ...

    @abstractmethod
    def stop(self) -> None:
        ...

    @abstractmethod
    def join(self) -> None:
        ...

    @property
    def diagnostics(self) -> dict:
        return {"ventilated": self._ventilated, "consumed": self._consumed,
                "requeued_items": self._requeued_items,
                "hung_workers_killed": self._hung_workers_killed,
                "hung_workers_abandoned": self._hung_workers_abandoned,
                "hedged_items": self._hedged_items,
                "hedge_wins": self._hedge_wins,
                "stopped": self._stopped}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        self.join()


class SerialExecutor(ExecutorBase):
    """Synchronous executor: work happens inside ``get`` (reference DummyPool,
    dummy_pool.py:20-91) - for tests, profiling, and debugging.

    The input queue is bounded so a Ventilator with ``num_epochs=None`` cannot
    enqueue unboundedly ahead of the consumer.

    Stall detection: work happens synchronously inside ``get``, so the
    reader-side stall loop (which only runs between ``get`` calls) can never
    observe a work item wedged inside user code.  ONE long-lived daemon
    watchdog thread (started lazily on the first ``get``) therefore polls a
    heartbeat slot: if ``fn(item)`` runs longer than
    ``PETASTORM_TPU_STALL_WARN_S`` a WARNING names the item (once per item).
    ``PETASTORM_TPU_STALL_ABORT_S`` remains inoperative here - synchronous
    user code cannot be safely interrupted from another thread; use the
    thread or process pool when abort matters (docs/operations.md).
    """

    def __init__(self, in_queue_size: int = 32, telemetry=None,
                 stop_on_failure: bool = True,
                 max_requeue_attempts: int = DEFAULT_REQUEUE_ATTEMPTS,
                 item_deadline_s: Optional[float] = None,
                 hedge_after_s=None,
                 stall_warn_s: Optional[float] = None):
        super().__init__(telemetry=telemetry, stop_on_failure=stop_on_failure,
                         max_requeue_attempts=max_requeue_attempts,
                         item_deadline_s=item_deadline_s,
                         hedge_after_s=hedge_after_s)
        if item_deadline_s is not None or hedge_after_s is not None:
            # same limitation as stall-abort: work runs synchronously inside
            # the consumer's get(), so there is no other worker to kill,
            # abandon, or hedge onto (docs/operations.md)
            logger.warning(
                "item_deadline_s/hedge_after_s are inoperative on the serial"
                " executor (work runs inline on the consumer thread); use the"
                " thread or process pool for liveness recovery")
        self._items: "queue.Queue[Any]" = queue.Queue(maxsize=in_queue_size)
        self._fn: Optional[Callable] = None
        self._in_queue_size = in_queue_size
        # per-item watchdog threshold: explicit kwarg (the reader's
        # stall_warn_s - the serial pool is the one flavor whose mid-item
        # stalls the reader-side loop cannot observe) wins over the env var
        self._stall_warn_s = (float(stall_warn_s) if stall_warn_s is not None
                              else _env_seconds("PETASTORM_TPU_STALL_WARN_S",
                                                120.0))
        # heartbeat slot for the watchdog (single writer: the get() caller;
        # same write-order contract as the thread pool's worker_state)
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_item: Any = None     # None = no item in flight
        self._watch_since = 0.0
        self._watch_gen = 0              # one warning per item, not per poll

    def start(self, worker_factory: WorkerFactory) -> None:
        self._fn = worker_factory()

    def inflight_capacity(self) -> int:
        """Serial work completes in ventilation order already; the reorder
        stage never holds more than the inline-retry window."""
        return int(self._in_queue_size) + 8

    def put(self, item: Any, cancel_event=None) -> None:
        t0 = time.perf_counter() if self._telemetry.enabled else None
        while not self._stopped:
            try:
                self._items.put(item, timeout=_POLL_S)
                self._ventilated += 1
                if t0 is not None:
                    self._m_input_full.add(time.perf_counter() - t0)
                return
            except queue.Full:
                if cancel_event is not None and cancel_event.is_set():
                    raise VentilationCancelled()
                continue
        raise ReaderClosedError("Executor is stopped")

    def _watch_loop(self) -> None:
        warned_gen = -1
        poll_s = min(max(self._stall_warn_s / 4.0, 0.05), 5.0)
        while not self._stopped:
            time.sleep(poll_s)
            item = self._watch_item
            if item is None:
                continue
            gen, elapsed = self._watch_gen, time.monotonic() - self._watch_since
            if elapsed > self._stall_warn_s and gen != warned_gen:
                warned_gen = gen
                logger.warning(
                    "Serial executor work item %s has run for %.0fs inside its"
                    " worker function (PETASTORM_TPU_STALL_WARN_S=%.0f);"
                    " pipeline state: %s", getattr(item, "ordinal", "?"),
                    elapsed, self._stall_warn_s, self.diagnostics)

    def get(self, timeout: Optional[float] = None) -> Any:
        if self._fn is None:
            raise PetastormTpuError("Executor not started")
        try:
            item = self._items.get(timeout=timeout or _POLL_S)
        except queue.Empty:
            raise queue.Empty("No ventilated items to process")
        if self._stall_warn_s > 0:
            if self._watch_thread is None:
                self._watch_thread = threading.Thread(
                    target=self._watch_loop, daemon=True,
                    name="petastorm-tpu-serial-watchdog")
                self._watch_thread.start()
            # timestamp and generation BEFORE the item (the watchdog guards
            # on item, so a non-None read sees current since/gen)
            self._watch_since = time.monotonic()
            self._watch_gen += 1
            self._watch_item = item
        current = item
        attempt = getattr(item, "attempt", 0)
        while True:
            try:
                try:
                    result = self._fn(current)
                finally:
                    self._watch_item = None
                # consumed = delivered, matching the thread/process pools:
                # a skipped/failed item must not inflate the count
                self._consumed += 1
                return result
            except BaseException as exc:  # noqa: BLE001 - classified below
                simulated = getattr(exc, "petastorm_tpu_simulated_crash",
                                    False)
                if not simulated and not isinstance(exc, Exception):
                    # KeyboardInterrupt / SystemExit / GeneratorExit are the
                    # CONSUMER's control flow (work runs inline here), never
                    # a work-item failure: propagate untouched in every mode
                    raise
                kind = "infra" if simulated else classify_error(exc)
                ordinal = getattr(current, "ordinal", None)
                if kind == "infra":
                    if attempt < self._max_requeue:
                        # serial "requeue": there is no surviving worker to
                        # move the item to, so retry it inline with the
                        # attempt count bumped (fault injection keys on it;
                        # the local counter bounds retries even for
                        # ordinal-less items)
                        attempt += 1
                        self._requeued_items += 1
                        self._m_requeued.add(1)
                        logger.warning(
                            "Serial worker infra failure on item %s (%s);"
                            " retrying inline (attempt %d/%d)", ordinal,
                            type(exc).__name__, attempt, self._max_requeue)
                        if ordinal is not None:
                            current = VentilatedItem(
                                ordinal, getattr(current, "item", current),
                                attempt)
                        self._watch_since = time.monotonic()
                        self._watch_gen += 1
                        self._watch_item = current
                        continue
                    # budget spent: a classified WorkerError in BOTH modes,
                    # matching the thread/process pools (and a raw
                    # SimulatedWorkerCrash BaseException must never escape
                    # to callers that handle `except Exception`)
                    raise _worker_error(exc, kind, ordinal, current) from exc
                if not self._stop_on_failure:
                    # skip-policy mode: deliver a classified WorkerError the
                    # reader can quarantine, without killing the executor
                    raise _worker_error(exc, kind, ordinal, current) from exc
                raise  # raise mode: propagate the original exception as-is

    def stop(self) -> None:
        self._stopped = True

    def join(self) -> None:
        pass

    @property
    def diagnostics(self) -> dict:
        return {**super().diagnostics,
                "in_queue_size": self._items.qsize()}


class ThreadedExecutor(ExecutorBase):
    """Bounded-queue thread pool (reference ThreadPool, thread_pool.py:78-221).

    pyarrow IO/decompress and cv2 decode release the GIL, so threads scale on
    multi-core TPU host VMs with zero serialization cost.
    """

    def __init__(self, workers_count: int = 3,
                 results_queue_size: int = DEFAULT_RESULTS_QUEUE_SIZE,
                 in_queue_size: Optional[int] = None,
                 profiling_enabled: bool = False,
                 telemetry=None,
                 stop_on_failure: bool = True,
                 max_requeue_attempts: int = DEFAULT_REQUEUE_ATTEMPTS,
                 item_deadline_s: Optional[float] = None,
                 hedge_after_s=None):
        super().__init__(telemetry=telemetry, stop_on_failure=stop_on_failure,
                         max_requeue_attempts=max_requeue_attempts,
                         item_deadline_s=item_deadline_s,
                         hedge_after_s=hedge_after_s)
        self._workers_count = workers_count
        # Queue choice is correctness-driven (round-5 hang post-mortem):
        # CPython's SimpleQueue.get(timeout) WEDGES under multiple
        # concurrent consumers — when a waiter wins the internal lock but a
        # sibling steals the item before it reacquires the GIL, the
        # remaining timeout is recomputed without clamping and a negative
        # value means an INFINITE lock wait (confirmed by disassembly and
        # reproduced standalone: tools/simplequeue_wedge_repro.py; it froze
        # a full suite run via this very pool).  _in_queue has N worker
        # consumers, so it uses the pure-python queue.Queue, whose
        # Condition-based timeout is correct by construction.  The output
        # side keeps the faster C SimpleQueue: it has exactly ONE consumer
        # (the reader thread), which closes the steal window.  Bounds live
        # in the semaphores either way (reference bounds ventilation at
        # workers_count + 2, reader.py:45-47,412, and treats a non-positive
        # results size as unbounded).
        self._in_queue: "queue.Queue[Any]" = queue.Queue()
        # resizable bounds (petastorm_tpu.autotune adjusts them mid-flight);
        # same exact-accounting contract as the BoundedSemaphores they
        # replaced - see _ResizableSemaphore
        self._in_size_explicit = in_queue_size is not None
        self._in_slots = _ResizableSemaphore(in_queue_size or workers_count + 2)
        self._out_queue: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
        self._out_slots = _ResizableSemaphore(
            results_queue_size if results_queue_size > 0 else 2 ** 30)
        self._stop_event = threading.Event()
        self._threads = []
        self._worker_factory: Optional[WorkerFactory] = None
        # dynamic resize (docs/operations.md "Autotuning"): slots told to
        # retire at their next item boundary, and slots that have retired.
        # A retiring worker finishes its current item, moves itself from
        # _retiring to _retired and exits; retired slots are excluded from
        # fault reaping, liveness accounting and the all-dead check.
        self._retiring: set = set()
        self._retired: set = set()
        self._resize_lock = threading.Lock()
        # True once resize_workers has been called: the worker count is then
        # an explicit TARGET the pool maintains (a slot lost to a death or an
        # abandoned hang is respawned - the thread flavor of the process
        # pool's kill-and-replace).  Never-resized pools keep the static
        # degrade-then-raise semantics PR 3 documented and tests pin.
        self._target_managed = False
        # opt-in worker profiling (reference per-thread cProfile,
        # thread_pool.py:41-49,190-198).  Python 3.12 allows only ONE active
        # profiler process-wide (sys.monitoring), so profiling is SAMPLED: a
        # single designated worker thread is profiled; workers are homogeneous,
        # so its profile is representative of all of them.
        self._profiling_enabled = profiling_enabled
        self._profiles = []
        self._profiles_lock = threading.Lock()
        # per-worker heartbeat: [ordinal-or-None, monotonic-since].  Written
        # only by the owning worker (single-writer per slot, no lock needed);
        # read by diagnostics to attribute a pipeline stall to the exact
        # worker and work item.
        self._worker_state: list = []
        # fault servicing (consumer-thread-only state): worker indexes whose
        # death has been handled
        self._reaped: set = set()
        # liveness (consumer-thread-only): index -> ordinal it was abandoned
        # on.  A thread cannot be SIGKILLed, so a worker hung past
        # item_deadline_s is ABANDONED: its slot stops counting as a live
        # worker, its item is requeued onto a sibling, and its eventual late
        # result (if the hang ever resolves) is dropped by the ledger.  The
        # entry clears itself if the thread recovers and takes a new item.
        self._abandoned: dict = {}

    def start(self, worker_factory: WorkerFactory) -> None:
        if self._threads:
            raise PetastormTpuError("Executor already started")
        # kept for dynamic grow (resize_workers spawns more slots from it)
        self._worker_factory = worker_factory
        for i in range(self._workers_count):
            fn = worker_factory()
            self._worker_state.append([None, time.monotonic()])
            t = threading.Thread(target=self._worker_loop,
                                 args=(fn, i, self._profiling_enabled and i == 0),
                                 name=f"petastorm-tpu-worker-{i}", daemon=True)
            t.start()
            self._threads.append(t)

    def resize_workers(self, n: int) -> int:
        """Grow or shrink the live worker plane to ``n`` threads in place
        (petastorm_tpu.autotune's worker knob; also callable directly).

        Grow spawns fresh worker threads from the factory captured at
        ``start``.  Shrink RETIRES the highest-index live slots: each marked
        worker finishes its current item (no item is ever dropped), then
        exits; its daemon thread, per-slot heartbeat, and the in-flight
        ledger all settle exactly as for a normal completion, so the
        semaphore accounting and the per-ordinal ledger stay exact across
        any resize sequence.  The default input-queue bound tracks
        ``workers + 2`` (an explicit ``in_queue_size`` is left alone).
        Returns the new target count.
        """
        n = max(1, int(n))
        with self._resize_lock:
            self._target_managed = True
            if not self._threads:  # not started: just update the target
                self._workers_count = n
                if not self._in_size_explicit:
                    self._in_slots.set_bound(n + 2)
                return n
            active = self._active_slots()
            if len(active) < n:
                for _ in range(n - len(active)):
                    active.append(self._spawn_slot())
            elif len(active) > n:
                for i in sorted(active, reverse=True)[:len(active) - n]:
                    self._retiring.add(i)
            self._workers_count = n
            if not self._in_size_explicit:
                self._in_slots.set_bound(n + 2)
            return n

    def _active_slots(self) -> list:
        """Indexes of slots that are part of the live worker plane."""
        return [i for i, t in enumerate(self._threads)
                if i not in self._retired and i not in self._retiring
                and i not in self._abandoned and t.is_alive()]

    def _spawn_slot(self) -> int:
        """Start a fresh worker slot, reusing a cleanly-retired slot index
        when one is free, else appending (hold _resize_lock).  Reuse matters
        under autotune: perpetual shrink/grow explore probes would otherwise
        grow ``_threads``/``_worker_state`` without bound, and every fault
        and deadline sweep walks those lists (the process pool already
        respawns into retired slots)."""
        fn = self._worker_factory()
        for i in sorted(self._retired):
            # only slots whose thread has fully exited (a retiring worker
            # marks itself retired just before returning, so a live thread
            # here is mid-exit - it stays reusable for the next grow)
            if not self._threads[i].is_alive():
                self._retired.discard(i)
                self._worker_state[i] = [None, time.monotonic()]
                t = threading.Thread(
                    target=self._worker_loop, args=(fn, i, False),
                    name=f"petastorm-tpu-worker-{i}", daemon=True)
                t.start()
                self._threads[i] = t
                return i
        i = len(self._worker_state)
        # state slot BEFORE the thread list entry: concurrent iterators
        # index worker_state by thread index, so len(threads) <=
        # len(worker_state) must always hold
        self._worker_state.append([None, time.monotonic()])
        t = threading.Thread(target=self._worker_loop, args=(fn, i, False),
                             name=f"petastorm-tpu-worker-{i}", daemon=True)
        t.start()
        self._threads.append(t)
        return i

    def _heal_to_target(self) -> None:
        """Respawn lost slots up to the managed target.  Only once
        resize_workers has put the plane under target management: with a
        controller (or caller) owning the worker count, a slot written off
        to a death or a hung-abandonment must not silently shrink the pool
        below its target - items requeued through the attempt budget need a
        live worker to land on (a shrunk-to-one pool whose survivor hangs
        would otherwise end the epoch with an all-abandoned raise)."""
        if not self._target_managed or not self._threads:
            return
        with self._resize_lock:
            for _ in range(self._workers_count - len(self._active_slots())):
                self._spawn_slot()

    def _trim_recovered(self, index: int) -> None:
        """Retire a just-recovered abandoned slot when it overshoots the
        managed target.  Abandonment on a target-managed pool heals in a
        replacement immediately; a thread cannot be killed, so if its hang
        later resolves the plane would hold target+1 live workers - and
        repeated slow-then-recovering items would grow it monotonically.
        The recovered slot (not the replacement) is the one retired: it
        finishes any in-flight item first, so nothing is dropped."""
        if not self._target_managed:
            return
        with self._resize_lock:
            if len(self._active_slots()) > self._workers_count:
                self._retiring.add(index)

    def set_results_bound(self, n: int) -> int:
        """Resize the results-queue bound in place (autotune's queue knob);
        shrinking below the current depth just blocks producers until the
        consumer drains under the new bound.  Returns the new bound."""
        n = max(1, int(n))
        self._out_slots.set_bound(n)
        return n

    def inflight_capacity(self) -> int:
        """Upper bound on distinct work items simultaneously in flight
        across the input queue, worker slots and results plane (plus slack
        for requeues racing fresh ventilation).  The deterministic reorder
        stage (Reader) uses this to tell "waiting on a straggler" apart
        from "the expected ordinal is in nobody's ledger"."""
        workers = max(len(self._threads), int(self._workers_count))
        return (int(self._in_slots.bound) + workers
                + int(self._out_slots.bound) + workers + 8)

    def _worker_loop(self, fn: Callable, index: int = 0,
                     profile_this_worker: bool = False) -> None:
        state = self._worker_state[index]
        profile = None
        if profile_this_worker:
            import cProfile

            profile = cProfile.Profile()
        while not self._stop_event.is_set():
            if index in self._retiring:
                # retire at the item boundary: mark retired BEFORE exiting so
                # the consumer's reap sweep never mistakes this clean exit
                # for a worker death (the thread stays alive until return).
                # The two set moves are atomic under _resize_lock: a resize
                # landing between them would see this slot in NEITHER set,
                # count it active, and re-retire it - stranding the slot in
                # both sets so its next reuse instantly self-retires
                state[0] = None
                state[1] = time.monotonic()
                with self._resize_lock:
                    self._retiring.discard(index)
                    self._retired.add(index)
                break
            try:
                item = self._in_queue.get(timeout=_POLL_S)
            except queue.Empty:
                continue
            self._in_slots.release()
            # timestamp BEFORE ordinal: a concurrent diagnostics read between
            # the two writes must never pair the new item with the old
            # idle-since time (it would report the whole idle gap as "stuck")
            state[1] = time.monotonic()
            ordinal = getattr(item, "ordinal", None)
            state[0] = ordinal if ordinal is not None else "?"
            try:
                if profile is not None:
                    try:
                        result = profile.runcall(fn, item)
                    except ValueError as exc:
                        # py3.12 allows one active profiler process-wide; if
                        # someone else holds it (second profiling pool, or the
                        # app itself under cProfile), degrade to unprofiled
                        # instead of failing the read
                        if "profiling tool" not in str(exc):
                            raise
                        logger.warning("Worker profiling disabled: %s", exc)
                        profile = None
                        result = fn(item)
                else:
                    result = fn(item)
            except BaseException as exc:  # noqa: BLE001 - forwarded to consumer
                if getattr(exc, "petastorm_tpu_simulated_crash", False):
                    # chaos harness: die like a hard-killed worker - no
                    # result, heartbeat left set so get() can attribute the
                    # lost item and requeue it onto surviving workers
                    return
                result = _Failure(exc, ordinal=ordinal, item=item)
            else:
                result = _Ok(ordinal, result, getattr(item, "attempt", 0))
            self._put_result_stop_aware(result)
            state[0] = None
            state[1] = time.monotonic()
        if profile is not None:
            with self._profiles_lock:
                self._profiles.append(profile)

    def _put_result_stop_aware(self, value: Any) -> None:
        # reference _stop_aware_put (thread_pool.py:200-214): bound via the
        # slot semaphore, never block indefinitely across a stop
        tele = self._telemetry
        if not tele.enabled:
            if self._acquire_result_slot():
                self._out_queue.put(value)
            return
        t0 = time.perf_counter()
        if self._stop_event.is_set():
            return
        if not self._out_slots.acquire(blocking=False):
            # the slot is not free at once: the wait is a 'results-wait'
            # span on this worker's timeline
            with tele.span("results-wait",
                           ordinal=getattr(value, "ordinal", None)):
                if not self._acquire_result_slot():
                    return
        self._out_queue.put(value)
        # time this worker spent blocked on a full results queue:
        # sustained values mean the CONSUMER is the bottleneck
        self._m_results_full.add(time.perf_counter() - t0)

    def _acquire_result_slot(self) -> bool:
        """A results-queue slot, polled so a stop is never slept through;
        False once stopped."""
        while not self._stop_event.is_set():
            if self._out_slots.acquire(timeout=_POLL_S):
                return True
        return False

    def put(self, item: Any, cancel_event=None) -> None:
        if self._stopped:
            raise ReaderClosedError("Executor is stopped")
        t0 = time.perf_counter() if self._telemetry.enabled else None
        while not self._stop_event.is_set():
            if self._in_slots.acquire(timeout=_POLL_S):
                self._track_put(item)
                self._in_queue.put(item)
                self._ventilated += 1
                if t0 is not None:
                    # time the ventilator spent blocked on a full input queue:
                    # the worker plane is saturated (healthy backpressure)
                    self._m_input_full.add(time.perf_counter() - t0)
                    self._g_in_depth.set(self._in_queue.qsize())
                return
            if cancel_event is not None and cancel_event.is_set():
                # caller withdrew the put while the queue was full (quiesce
                # with a saturated pipeline); the item was NOT enqueued
                raise VentilationCancelled()
        raise ReaderClosedError("Executor stopped while putting")

    def _try_enqueue(self, item: Any) -> bool:
        # consumer-thread context (called from get); never block on a full
        # input queue here - the caller parks the item and retries later
        if self._in_slots.acquire(blocking=False):
            self._in_queue.put(item)
            return True
        return False

    def _service_faults(self) -> None:
        """Reap dead worker threads (requeueing their in-flight items) and
        flush parked requeues.  Runs on the consumer thread between polls -
        deliberately: every liveness mutation (requeue parking, abandonment,
        hedging) stays consumer-thread-only state, so no new locks and no
        races with a separate watchdog thread."""
        self._flush_pending_requeues()
        if self._stop_event.is_set():
            return
        for i, t in enumerate(self._threads):
            if t.is_alive() or i in self._reaped or i in self._retired:
                continue
            # a dead thread still marked _retiring never reached its retire
            # bookkeeping: it died INSIDE fn (e.g. a simulated crash), so it
            # is a genuine death, not a clean retirement
            self._retiring.discard(i)
            self._reaped.add(i)
            ordinal = self._worker_state[i][0]
            logger.warning("Worker thread %d died while on item %s", i,
                           ordinal)
            # clear the dead worker's busy slot BEFORE the (possibly
            # raising) requeue: diagnostics must not report a phantom
            # stuck worker forever (the owner is dead, so this write
            # cannot race it)
            self._worker_state[i][1] = time.monotonic()
            self._worker_state[i][0] = None
            # replace BEFORE the (possibly raising) requeue: a target-managed
            # pool must keep its worker count whether or not the item has
            # budget left
            self._heal_to_target()
            self._requeue_lost(ordinal if isinstance(ordinal, int) else None,
                               f"worker thread {i} death")
        self._check_liveness()
        considered = [(i, t) for i, t in enumerate(self._threads)
                      if i not in self._retired]
        if ((self._reaped or self._abandoned) and considered
                and all(not t.is_alive() or i in self._abandoned
                        for i, t in considered)
                and self._out_queue.empty()):
            # abandoned-as-hung slots count as gone: with every worker dead
            # or written off, queued/requeued items have no one to run them
            # - raising here is the difference between a classified error
            # and the exact indefinite wedge item_deadline_s exists to end
            if self._stop_on_failure:
                self.stop()
            raise WorkerError("All worker threads died or were abandoned as"
                              " hung; no result will arrive", kind="infra")

    def _check_liveness(self) -> None:
        """Item-deadline + hedging sweep over the worker heartbeats
        (consumer-thread context; polled while the consumer waits, which is
        exactly when a hung or straggling item matters).

        Deadline: a slot busy on the same item past ``item_deadline_s`` is
        abandoned (threads cannot be killed; the daemonic thread is excluded
        from liveness accounting and from close-time joins) and its item is
        requeued through the attempt budget - exhaustion surfaces a
        ``'data'``-kind WorkerError so a repeatedly-hanging item quarantines
        under a skip policy.  Hedging: a slot busy past the hedge threshold
        gets its item speculatively re-issued when an idle worker exists;
        the in-flight ledger keeps delivery exactly-once either way.
        """
        deadline = self._item_deadline_s
        hedge_s = (self._hedge_threshold()
                   if self._hedge_after is not None else None)
        if deadline is None and hedge_s is None:
            return
        now = time.monotonic()
        # iterate the thread list, not worker_state: a concurrent grow
        # appends the state slot first, so indexes past len(threads) may
        # exist transiently.  Retired/retiring slots are no longer part of
        # the live worker plane (idle-for-hedging or deadline sweeps).
        idle = any(s[0] is None for i, s in enumerate(self._worker_state)
                   if i < len(self._threads) and i not in self._abandoned
                   and i not in self._retired and i not in self._retiring
                   and self._threads[i].is_alive())
        for i, t in enumerate(self._threads):
            if i in self._retired:
                continue
            s = self._worker_state[i]
            ordinal = s[0]
            if ordinal is None:
                if self._abandoned.pop(i, None) is not None:
                    self._trim_recovered(i)  # recovered and went idle
                continue
            if self._abandoned.get(i) == ordinal:
                continue  # already handled this hang
            if i in self._abandoned:
                del self._abandoned[i]  # recovered onto a new item
                self._trim_recovered(i)
            if not t.is_alive():
                continue  # the reap path owns dead workers
            elapsed = max(0.0, now - s[1])
            if deadline is not None and elapsed > deadline:
                self._abandoned[i] = ordinal
                self._hung_workers_abandoned += 1
                self._m_hung_abandoned.add(1)
                logger.warning(
                    "Worker thread %d hung on item %s for %.1fs >"
                    " item_deadline_s=%.1f; abandoning the slot and"
                    " requeueing the item onto a sibling worker", i, ordinal,
                    elapsed, deadline)
                # a target-managed pool replaces the written-off slot before
                # the (possibly raising) requeue - same contract as the
                # process pool's kill-and-replace
                self._heal_to_target()
                self._requeue_lost(
                    ordinal if isinstance(ordinal, int) else None,
                    f"hung worker thread {i} (exceeded item deadline"
                    f" {deadline:.1f}s)", exhausted_kind="data")
                continue
            if (hedge_s is not None and elapsed > hedge_s and idle
                    and self._hedge(
                        ordinal if isinstance(ordinal, int) else None,
                        f"straggling {elapsed:.1f}s on worker thread {i}"
                        f" (hedge threshold {hedge_s:.1f}s)")):
                idle = False  # one speculative copy per sweep

    def get(self, timeout: Optional[float] = None) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            try:
                result = self._out_queue.get(timeout=_POLL_S)
            except queue.Empty:
                self._service_faults()
                if deadline is not None and time.monotonic() >= deadline:
                    raise
                continue
            # releases are bounded by successful gets, which are bounded by
            # acquired puts: a ValueError here would be a real accounting bug
            self._out_slots.release()
            if isinstance(result, _Failure):
                if self._deliver_failure(result):
                    continue  # infra failure absorbed by a requeue
            if not self._settle(result.ordinal):
                # requeue duplicate (original result surfaced after its
                # worker died): drop it - the first delivery already counted
                continue
            self._note_delivery(result.ordinal, getattr(result, "attempt", 0))
            self._consumed += 1
            if self._telemetry.enabled:
                self._g_out_depth.set(self._out_queue.qsize())
                self._g_in_depth.set(self._in_queue.qsize())
            return result.value

    def stop(self) -> None:
        self._stopped = True
        self._stop_event.set()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for worker threads.  ``timeout`` (total, across all workers)
        bounds the wait when a worker may be wedged inside user code — e.g.
        after a stall abort: the threads are daemonic, so abandoning them
        cannot block process exit, and a warning names what was abandoned."""
        if not self._stopped:
            raise PetastormTpuError("call stop() before join()")
        if timeout is None and (self._item_deadline_s is not None
                                or self._hedge_after is not None):
            # liveness-enabled pools already accept abandoning wedged daemon
            # workers mid-epoch; an unbounded close-time join would trade the
            # hang the deadline/hedge just recovered from for a close hang
            timeout = 5.0
        deadline = None if timeout is None else time.monotonic() + timeout
        for i, t in enumerate(self._threads):
            if i in self._abandoned:
                continue  # known-hung: daemonic, never joins - skip the wait
            t.join(None if deadline is None
                   else max(0.0, deadline - time.monotonic()))
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            logger.warning(
                "Abandoning %d wedged daemon worker thread(s) %s after %.0fs;"
                " pipeline state: %s", len(alive), alive, timeout or 0,
                self.diagnostics)
        if self._profiling_enabled and self._profiles:
            stats = self.profile_stats()
            if stats is not None:
                import io as _io

                out = _io.StringIO()
                stats.stream = out
                stats.sort_stats("cumulative").print_stats(20)
                logger.info("Sampled worker profile (top 20 by cumulative):\n%s",
                            out.getvalue())

    def profile_stats(self):
        """``pstats.Stats`` of the sampled worker thread, or None when
        profiling was off / the sampled worker ran no item yet."""
        import pstats

        with self._profiles_lock:
            profiles = [p for p in self._profiles if p.getstats()]
            if not profiles:
                return None
            stats = pstats.Stats(profiles[0])
            for p in profiles[1:]:
                stats.add(p)
            return stats

    @property
    def diagnostics(self) -> dict:
        now = time.monotonic()
        # snapshot each slot's ordinal ONCE: the worker may clear it between
        # a guard and a second read, which would emit a spurious None entry
        busy = []
        for i, s in enumerate(self._worker_state):
            ordinal = s[0]
            if ordinal is not None:
                # clamp: the worker may stamp a newer time between our `now`
                # snapshot and this read
                busy.append((i, ordinal, round(max(0.0, now - s[1]), 3)))
        return {**super().diagnostics,
                "in_queue_size": self._in_queue.qsize(),
                "results_queue_size": self._out_queue.qsize(),
                "workers_count": self._workers_count,
                # resizable bounds (autotune knobs) + retired-slot count so a
                # resize trajectory is reconstructible post-mortem
                "in_queue_bound": self._in_slots.bound,
                "results_queue_bound": self._out_slots.bound,
                "workers_retired": len(self._retired),
                # [(worker index, item ordinal, seconds on it)] for workers
                # currently inside fn(item) - a stalled pipeline names the
                # exact worker and work item instead of wedging silently
                "workers_busy": busy,
                # liveness: slots written off as hung (still daemon-alive,
                # excluded from worker accounting and close-time joins)
                "workers_abandoned": sorted(self._abandoned)}


class _CrashSafeResultsChannel:
    """Bounded results transport whose writes happen synchronously in the
    worker's only thread.

    ``mp.Queue`` delivers through a per-process background *feeder* thread
    that serializes frames onto a pipe shared by every writer, under a
    shared write lock.  A worker that dies abruptly (OOM kill, chaos
    ``os._exit``) while its feeder holds that lock abandons the lock: every
    surviving worker's feeder then blocks forever, the consumer starves on
    an apparently non-empty queue (``qsize`` counts buffered puts that will
    never reach the pipe), and the epoch wedges with a live-but-mute worker
    plane.  Reproduced as the intermittent chaos-kill hang in
    tests/test_fault_tolerance.py::test_chaos_e2e_poison_kill_and_weather.

    Here ``put`` sends the frame from the worker's MAIN thread (its only
    thread) under a cross-process lock.  The abrupt-death styles this pool
    must survive land inside ``fn`` (chaos ``os._exit``, the simulated-crash
    hook) or via the liveness SIGKILL sweep, which already refuses to kill
    a delivering worker - so a death can no longer interleave with a
    half-written frame or an abandoned write lock.  Backpressure comes from
    a slot semaphore (acquired by the writer, released by the consumer
    after ``recv``), matching ``mp.Queue(maxsize)`` semantics;
    ``bound <= 0`` means unbounded, like ``mp.Queue``.

    Two deliberate residual tradeoffs.  (1) A death the pool does NOT
    control - a kernel OOM kill or external SIGKILL landing exactly inside
    ``send`` - can still orphan the write lock and leave a partial frame
    that blocks the consumer's ``recv`` past its poll timeout; the pool's
    own kill paths cannot land there, and ``mp.Queue`` wedged under a
    strictly larger set of death styles.  (2) Sends serialize under the one
    write lock, so siblings queue behind a large in-flight frame; with the
    shm transport (the default where the native module builds) frames are
    small descriptors and the lock is held microseconds.  Per-worker pipes
    would remove both by construction - the upgrade path if either bites.
    """

    def __init__(self, ctx, bound: int):
        self._rx, self._tx = ctx.Pipe(duplex=False)
        self._wlock = ctx.Lock()
        self._bound = int(bound)
        self._slots = ctx.BoundedSemaphore(self._bound) if bound > 0 else None

    def put(self, obj, stop_event, wait_cell=None) -> bool:
        """Worker-side enqueue; False = dropped (shutdown/closed channel).

        ``wait_cell``: optional ``(shared double array, slot index)`` that
        accumulates the seconds this worker spent BLOCKED on a full channel
        (slot-semaphore waits only; an uncontended acquire records nothing).
        Single-writer per slot; the parent harvests deltas into the
        ``queue.results_full_wait_s`` counter so the autotune controller's
        consumer-bound signal works across the process boundary."""
        if self._slots is not None and not self._slots.acquire(block=False):
            t0 = time.perf_counter()
            while not self._slots.acquire(timeout=_POLL_S):
                if stop_event.is_set():
                    return False
            if wait_cell is not None:
                arr, i = wait_cell
                arr[i] += time.perf_counter() - t0
        try:
            with self._wlock:
                self._tx.send(obj)
        except (OSError, ValueError):
            # consumer gone (read end closed at join); nothing to deliver to
            if self._slots is not None:
                try:
                    self._slots.release()
                except ValueError:
                    pass
            return False
        return True

    def get(self, timeout: Optional[float] = None):
        """Parent-side dequeue; raises ``queue.Empty`` on timeout (the
        ``mp.Queue.get`` contract the poll loops are written against)."""
        if not self._rx.poll(timeout):
            raise queue.Empty
        obj = self._rx.recv()
        if self._slots is not None:
            try:
                self._slots.release()
            except ValueError:
                pass
        return obj

    def qsize(self) -> int:
        if self._slots is None:
            raise NotImplementedError("unbounded channel has no depth gauge")
        # in-flight = bound - free slots (sem_getvalue; absent on macOS,
        # where this raises NotImplementedError like mp.Queue.qsize)
        return self._bound - self._slots.get_value()

    def worker_init(self) -> None:
        """Child-side setup: drop the inherited read end.  Every spawned
        worker receives a dup of ``_rx`` through the Process args; while any
        of those dups stays open, the parent's :meth:`close` cannot turn a
        blocked ``send`` into an EPIPE - the pipe would still have a
        nominal reader."""
        try:
            self._rx.close()
        except OSError:
            pass

    def close(self) -> None:
        """Parent-side teardown: closing the read end makes any sender
        still blocked in ``send`` fail with EPIPE instead of leaking
        (requires every worker to have dropped its inherited ``_rx`` dup
        via :meth:`worker_init`)."""
        for conn in (self._rx, self._tx):
            try:
                conn.close()
            except OSError:
                pass


def _process_worker_main(worker_factory, in_queue, out_queue, stop_event,
                         index=0, heartbeats=None, retire_flags=None,
                         full_waits=None):
    """Worker-process entrypoint (module-level: must be picklable for spawn).

    ``heartbeats``: optional lock-free shared double array, 3 slots per
    worker: [ordinal (-1 = idle), wall-clock since, delivering flag] — same
    stall-attribution contract as ThreadedExecutor's ``workers_busy``,
    crossing the process boundary via shared memory.  Wall clock
    (time.time), not monotonic: monotonic clocks are not comparable across
    processes on all platforms.  Reads of the (ordinal, since) PAIR can
    tear: each 8-byte slot is individually atomic but the pair is not.  The
    write order here (timestamp BEFORE ordinal) plus the double-read
    validation on the reading side (``_ProcessExecutor._read_heartbeat``:
    ordinal, timestamp, ordinal again, retry when the ordinal moved)
    guarantees a sample never pairs a new ordinal with a stale timestamp —
    a torn pair can no longer report a bogus stall (PR 1 caveat, since
    fixed).  One residual caveat alongside the wall-clock one: the 8-byte
    slot writes themselves are plain unsynchronized RawArray stores, and
    their per-slot atomicity is an x86-64 property (aligned 8-byte stores
    are single-copy atomic there).  On architectures without that guarantee
    a reader could in principle observe a HALF-WRITTEN double inside one
    slot — bounded to one garbage (ordinal, since) sample in a diagnostics
    sweep (the next sweep re-reads fresh values, and the reading side
    clamps negative ages), never control-flow corruption, since the kill
    sweep re-reads post-mortem before acting.

    ``retire_flags``: optional shared byte array, one flag per slot; a
    nonzero flag tells this worker to exit cleanly at its next item
    boundary (dynamic pool shrink, ``_ProcessExecutor.resize_workers``).
    The current item always completes and delivers first.

    ``full_waits``: optional shared double array, one cell per slot,
    accumulating the seconds this worker spent blocked on a full results
    channel (single writer per cell, same torn-store caveat as the
    heartbeats).  The parent folds deltas into the
    ``queue.results_full_wait_s`` counter on its ``get()`` path, so the
    autotune controller's consumer-bound signal crosses the process
    boundary.

    The heartbeat doubles as the crash ledger: a worker that dies mid-item
    (OOM kill, segfault) leaves its ordinal in the slot, which is how the
    parent knows exactly which work item to requeue onto surviving workers.

    The ``delivering`` slot (-1.0 = no) flips to the ordinal between
    finishing the work function and completing the result enqueue.  The
    liveness kill sweep (``_check_liveness``) refuses to SIGKILL a
    delivering worker: a kill landing inside the channel's ``send`` would
    orphan the shared write lock and deadlock every other worker's
    ``out_queue.put`` forever (``_CrashSafeResultsChannel`` keeps every
    OTHER abrupt-death style off that lock by sending from this thread).
    The ordinal slot deliberately stays set until AFTER the put, preserving
    crash attribution for a death mid-delivery (the ledger requeues it; a
    double delivery dedups).
    """
    out_queue.worker_init()  # drop the inherited read end (see channel docs)
    try:
        fn = worker_factory()
    except BaseException as exc:  # noqa: BLE001
        out_queue.put(_Failure(exc), stop_event)
        return
    if hasattr(fn, "stop_event"):  # shm encoder: abort full-arena waits on stop
        fn.stop_event = stop_event
    base = 3 * index
    while not stop_event.is_set():
        if retire_flags is not None and retire_flags[index]:
            # retire at the item boundary (pool shrink): ack with 2 BEFORE
            # exiting so the parent can promote the slot to retired without
            # having to observe the process death in a fault sweep
            retire_flags[index] = 2
            break
        try:
            item = in_queue.get(timeout=_POLL_S)
        except queue.Empty:
            continue
        if item is _ProcessExecutor._STOP_SENTINEL_VALUE:
            break
        ordinal = getattr(item, "ordinal", None)
        try:
            hb_ordinal = float(ordinal)
        except (TypeError, ValueError):
            hb_ordinal = -2.0  # busy, ordinal unknown
        if heartbeats is not None:
            # timestamp before ordinal (same reasoning as the thread pool:
            # a concurrent read must never pair a new item with an old time)
            heartbeats[base + 1] = time.time()
            heartbeats[base] = hb_ordinal
        try:
            result = _Ok(ordinal, fn(item), getattr(item, "attempt", 0))
        except BaseException as exc:  # noqa: BLE001
            if getattr(exc, "petastorm_tpu_simulated_crash", False):
                # chaos harness: die exactly like an OOM kill - no result,
                # no traceback, heartbeat left naming the in-flight item
                os._exit(17)
            result = _Failure(exc, ordinal=ordinal, item=item)
        if heartbeats is not None:
            heartbeats[base + 2] = hb_ordinal  # delivering: do not SIGKILL
        out_queue.put(result, stop_event,
                      wait_cell=(None if full_waits is None
                                 else (full_waits, index)))
        if heartbeats is not None:
            heartbeats[base] = -1.0
            heartbeats[base + 1] = time.time()
            heartbeats[base + 2] = -1.0


class _ProcessExecutor(ExecutorBase):
    """Spawned multiprocessing pool for GIL-bound worker functions.

    Replaces the reference's zmq ProcessPool (process_pool.py:114-428): spawn
    semantics and exception forwarding are kept; the zmq data plane, startup
    barrier, and slow-joiner workarounds fall away because multiprocessing queues
    provide them.  Daemon processes make the parent-death watchdog
    (process_pool.py:324-331) unnecessary.
    """

    _STOP_SENTINEL_VALUE = "__petastorm_tpu_stop__"

    #: default shared-memory arena size for the native data plane
    DEFAULT_SHM_BYTES = 256 * 2**20

    def __init__(self, workers_count: int = 3,
                 results_queue_size: int = DEFAULT_RESULTS_QUEUE_SIZE,
                 in_queue_size: Optional[int] = None,
                 use_shm: Optional[bool] = None,
                 shm_size_bytes: int = DEFAULT_SHM_BYTES,
                 telemetry=None,
                 stop_on_failure: bool = True,
                 max_requeue_attempts: int = DEFAULT_REQUEUE_ATTEMPTS,
                 item_deadline_s: Optional[float] = None,
                 hedge_after_s=None,
                 max_workers: Optional[int] = None):
        # telemetry: the PARENT process records ventilation/queue waits;
        # worker-side stage metrics recorded in the spawned processes stay
        # there (PETASTORM_TPU_TELEMETRY is inherited, so each child records
        # independently) - thread pool gives one merged report
        super().__init__(telemetry=telemetry, stop_on_failure=stop_on_failure,
                         max_requeue_attempts=max_requeue_attempts,
                         item_deadline_s=item_deadline_s,
                         hedge_after_s=hedge_after_s)
        import multiprocessing as mp

        self._ctx = mp.get_context("spawn")
        self._workers_count = workers_count
        # shared-memory slot capacity for dynamic grow (resize_workers): the
        # heartbeat/retire RawArrays cannot be extended after start, so slots
        # are pre-allocated up to this ceiling.  ``max_workers`` (autotune's
        # policy bound) sizes it explicitly; the default leaves generous
        # headroom without materializing hundreds of unused slots.
        self._slot_capacity = max(workers_count,
                                  max_workers if max_workers
                                  else min(4 * workers_count, 32))
        self._in_queue_size = in_queue_size or workers_count + 2
        self._in_queue = self._ctx.Queue(self._in_queue_size)
        # NOT an mp.Queue: its async feeder thread can wedge every surviving
        # writer when a worker dies abruptly (see _CrashSafeResultsChannel)
        self._results_queue_size = results_queue_size
        self._out_queue = _CrashSafeResultsChannel(self._ctx,
                                                   results_queue_size)
        self._stop_event = self._ctx.Event()
        self._procs = []
        self._worker_factory = None
        self._reaped: set = set()
        # dynamic resize (docs/operations.md "Autotuning"): slots flagged to
        # retire at their next item boundary, and slots whose worker has
        # exited cleanly after retirement
        self._retiring: set = set()
        self._retired: set = set()
        self._retire_flags = None
        # RLock: resize_workers calls _promote_retirements (which now locks
        # itself - the consumer's fault sweep and diagnostics promote too,
        # and an unlocked promotion racing a locked grow can exile a freshly
        # respawned worker from fault reaping)
        self._resize_lock = threading.RLock()
        # True once resize_workers has been called: the count becomes an
        # explicit target the pool maintains, so a crashed worker is
        # respawned into its slot instead of permanently shrinking the plane
        # (never-resized pools keep the PR 2 degrade-then-raise semantics)
        self._target_managed = False
        self._arena = None
        self._heartbeats = None
        # per-slot full-channel wait accumulators (seconds), harvested into
        # queue.results_full_wait_s as deltas on the parent's get() path
        self._full_waits = None
        self._full_wait_harvested = 0.0
        self._shm_size_bytes = shm_size_bytes
        if use_shm is None:  # auto: use the native transport when it builds
            from petastorm_tpu.native import is_available

            use_shm = is_available()
        self._use_shm = use_shm

    def start(self, worker_factory: WorkerFactory) -> None:
        if self._procs:
            raise PetastormTpuError("Executor already started")
        if self._use_shm:
            from petastorm_tpu.native import SharedArena
            from petastorm_tpu.native.transport import ShmResultEncoder

            self._arena = SharedArena.create(self._shm_size_bytes)
            worker_factory = ShmResultEncoder(worker_factory, self._arena.name)
        # kept for hung-worker kill-and-replace respawns (_check_liveness)
        # and dynamic grow (resize_workers)
        self._worker_factory = worker_factory
        # lock-free heartbeat slots (single-writer per triple; see
        # _process_worker_main) - powers workers_busy across processes.
        # Allocated at slot CAPACITY, not current count: RawArrays cannot
        # grow, and resize_workers spawns into the spare slots.
        self._heartbeats = self._ctx.RawArray("d", 3 * self._slot_capacity)
        self._retire_flags = self._ctx.RawArray("b", self._slot_capacity)
        # single writer per cell (the slot's worker; same torn-store caveat
        # as the heartbeats); a respawn into the slot keeps accumulating
        self._full_waits = self._ctx.RawArray("d", self._slot_capacity)
        for i in range(self._slot_capacity):
            self._heartbeats[3 * i] = -1.0
            self._heartbeats[3 * i + 2] = -1.0
        for i in range(self._workers_count):
            self._procs.append(self._spawn_worker(i))

    def _spawn_worker(self, index: int):
        """Spawn (or respawn) the worker process for slot ``index``; the
        heartbeat pair at that index is reused (single writer at a time: a
        replacement is only spawned after its predecessor is confirmed
        dead)."""
        p = self._ctx.Process(
            target=_process_worker_main,
            args=(self._worker_factory, self._in_queue, self._out_queue,
                  self._stop_event, index, self._heartbeats,
                  self._retire_flags, self._full_waits),
            name=f"petastorm-tpu-worker-{index}", daemon=True)
        p.start()
        return p

    @property
    def max_resize_workers(self) -> int:
        """Hard ceiling on ``resize_workers`` targets (shared-memory slot
        capacity, fixed at construction)."""
        return self._slot_capacity

    def _promote_retirements(self) -> None:
        """Move retiring slots whose worker ACKED the retire flag (wrote 2
        at its item boundary, _process_worker_main) to retired.  The ack is
        written before the process exits, so promotion does not depend on a
        fault sweep happening to observe the death."""
        with self._resize_lock:
            for i in list(self._retiring):
                if self._retire_flags[i] == 2:
                    self._retiring.discard(i)
                    self._retired.add(i)
                    self._heartbeats[3 * i + 1] = time.time()
                    self._heartbeats[3 * i] = -1.0
                    self._heartbeats[3 * i + 2] = -1.0

    def inflight_capacity(self) -> int:
        """Upper bound on distinct work items simultaneously in flight (see
        ThreadedExecutor.inflight_capacity; same contract for the process
        plane: input queue + worker slots + results channel + slack)."""
        workers = max(len(self._procs), int(self._workers_count))
        results = (self._results_queue_size if self._results_queue_size > 0
                   else 2 ** 30)
        return int(self._in_queue_size) + workers + int(results) + workers + 8

    def resize_workers(self, n: int) -> int:
        """Grow or shrink the worker-process plane to ``n`` in place
        (petastorm_tpu.autotune's worker knob).

        Grow reuses cleanly-retired slots first (clearing their retire
        flag), then spawns into spare pre-allocated slots, capped at
        ``max_resize_workers``.  Shrink flags the highest-index live slots
        to retire: each worker finishes and DELIVERS its current item, then
        exits at the item boundary, so the per-ordinal ledger and epoch
        accounting stay exact.  Returns the new target count (clamped to
        the slot capacity).
        """
        n = max(1, min(int(n), self._slot_capacity))
        with self._resize_lock:
            self._target_managed = True
            if not self._procs:  # not started: just update the target
                self._workers_count = n
                return n
            self._promote_retirements()  # acked slots are reusable for grow
            active = [i for i, p in enumerate(self._procs)
                      if i not in self._retired and i not in self._retiring
                      and p.is_alive()]
            if len(active) < n:
                for i in sorted(self._retired):
                    if len(active) >= n:
                        break
                    self._retire_flags[i] = 0
                    self._retired.discard(i)
                    self._reaped.discard(i)
                    self._heartbeats[3 * i + 1] = time.time()
                    self._heartbeats[3 * i] = -1.0
                    self._heartbeats[3 * i + 2] = -1.0
                    self._procs[i] = self._spawn_worker(i)
                    active.append(i)
                while len(active) < n and len(self._procs) < self._slot_capacity:
                    i = len(self._procs)
                    self._procs.append(self._spawn_worker(i))
                    active.append(i)
            elif len(active) > n:
                for i in sorted(active, reverse=True)[:len(active) - n]:
                    self._retiring.add(i)
                    self._retire_flags[i] = 1
            self._workers_count = n
            return n

    def put(self, item: Any, cancel_event=None) -> None:
        if self._stopped:
            raise ReaderClosedError("Executor is stopped")
        t0 = time.perf_counter() if self._telemetry.enabled else None
        # ledger entry BEFORE the enqueue: a fast worker's result can reach
        # the consumer's _settle before this thread runs again, and an
        # unregistered ordinal would make that legitimate delivery look like
        # a requeue duplicate (silently dropped -> lost rows)
        self._track_put(item)
        try:
            while True:
                try:
                    self._in_queue.put(item, timeout=_POLL_S)
                    self._ventilated += 1
                    if t0 is not None:
                        self._m_input_full.add(time.perf_counter() - t0)
                        try:  # mp.Queue.qsize raises on some platforms
                            self._g_in_depth.set(self._in_queue.qsize())
                        except NotImplementedError:
                            pass
                    return
                except queue.Full:
                    if self._stopped:
                        raise ReaderClosedError("Executor stopped while putting")
                    if cancel_event is not None and cancel_event.is_set():
                        raise VentilationCancelled()
        except BaseException:
            # the item never made it into the queue: retract the ledger
            # entry so it cannot be mistaken for lost in-flight work
            self._settle(getattr(item, "ordinal", None))
            raise

    def _read_heartbeat(self, index: int):
        """Torn-read-safe sample of worker ``index``'s heartbeat pair.

        The worker writes timestamp-then-ordinal; each 8-byte slot is atomic
        but the pair is not.  Reading ordinal, timestamp, ordinal-again and
        retrying while the ordinal moved guarantees the returned timestamp
        belongs to (or postdates) the returned ordinal - a torn pair can
        never pair a NEW ordinal with a STALE timestamp and report a bogus
        stall.  Returns (ordinal float, since float): -1.0 = idle, -2.0 =
        busy on an ordinal-less item.
        """
        hb = self._heartbeats
        base = 3 * index
        ordinal = hb[base]
        since = hb[base + 1]
        for _ in range(3):
            again = hb[base]
            if again == ordinal:
                break
            ordinal = again
            since = hb[base + 1]
        return ordinal, since

    def _is_delivering(self, index: int) -> bool:
        """True while worker ``index`` is between finishing its work
        function and completing the result enqueue (kill-unsafe window)."""
        return self._heartbeats[3 * index + 2] != -1.0

    def _try_enqueue(self, item: Any) -> bool:
        try:
            self._in_queue.put_nowait(item)
            return True
        except queue.Full:
            return False

    def _service_faults(self) -> None:
        """Reap dead worker processes, requeueing the item each one held
        (named by its crash-ledger heartbeat), and flush parked requeues."""
        self._flush_pending_requeues()
        if self._stopped or self._stop_event.is_set():
            return
        self._promote_retirements()
        for i, p in enumerate(self._procs):
            if p.is_alive() or i in self._reaped or i in self._retired:
                continue
            with self._resize_lock:
                retiring = i in self._retiring
                if retiring:
                    # the flagged worker exited: a clean retirement unless
                    # its heartbeat still names an in-flight item (it died
                    # INSIDE fn while retiring - a genuine crash, requeue)
                    self._retiring.discard(i)
                    self._retired.add(i)
                    hb_ordinal, _since = self._read_heartbeat(i)
                    self._heartbeats[3 * i + 1] = time.time()
                    self._heartbeats[3 * i] = -1.0
                    self._heartbeats[3 * i + 2] = -1.0
            if retiring:
                if hb_ordinal >= 0:
                    self._requeue_lost(
                        int(hb_ordinal),
                        f"worker process {i} death during retirement"
                        f" (exit code {p.exitcode})")
                continue
            self._reaped.add(i)
            ordinal = None
            if self._heartbeats is not None:
                hb_ordinal, _since = self._read_heartbeat(i)
                if hb_ordinal >= 0:
                    ordinal = int(hb_ordinal)
                elif hb_ordinal == -2.0:
                    logger.warning(
                        "Worker process %d died holding an ordinal-less work"
                        " item; it cannot be requeued", i)
            logger.warning(
                "Worker process %d (pid %s) died with exit code %s while on"
                " item %s (possible crash/OOM)", i, p.pid, p.exitcode,
                ordinal if ordinal is not None else "<none>")
            if self._heartbeats is not None:
                # clear the crash ledger BEFORE the (possibly raising)
                # requeue so diagnostics never report a phantom stuck
                # worker (the owner is dead; no write race)
                self._heartbeats[3 * i + 1] = time.time()
                self._heartbeats[3 * i] = -1.0
                self._heartbeats[3 * i + 2] = -1.0
            if self._target_managed:
                # target-managed plane (resize_workers was called): respawn
                # the slot BEFORE the (possibly raising) requeue so the pool
                # holds its target whether or not the item has budget left -
                # but never overshoot it (this death may already be absorbed
                # by a pending shrink that excluded the dead slot)
                with self._resize_lock:
                    active = [j for j, q in enumerate(self._procs)
                              if j != i and j not in self._retired
                              and j not in self._retiring and q.is_alive()]
                    if len(active) < self._workers_count:
                        self._reaped.discard(i)
                        self._procs[i] = self._spawn_worker(i)
            self._requeue_lost(
                ordinal, f"worker process {i} death (exit code {p.exitcode})")
        self._check_liveness()
        # Residual window, deliberately NOT reconciled: a SIGKILL landing in
        # the few instructions between a worker's in_queue.get and its
        # heartbeat stamp loses the item without naming it (the ledger holds
        # it, nobody delivers it).  Detecting that state from here would
        # need mp.Queue emptiness, which is advisory (the feeder thread
        # buffers) - a reconciliation attempt built on it demonstrably
        # misfired on healthy pipelines.  The stall watchdog
        # (stall_warn_s / stall_abort_s) is the designated backstop for
        # exactly this class of unattributable loss.

    def _check_liveness(self) -> None:
        """Item-deadline + hedging sweep over the shared-memory heartbeats
        (consumer-thread context, like the requeue machinery).

        Deadline: a worker whose heartbeat names the same in-flight item for
        longer than ``item_deadline_s`` is SIGKILLed - the only interruption
        that reaches a worker wedged in a blocking C call or a deadlocked
        native library - and REPLACED with a fresh spawn at the same slot;
        the item is requeued through the attempt budget, so a genuinely
        poisoned slow item eventually surfaces as a quarantine-eligible
        ``'data'`` error.  Hedging: an item past the hedge threshold is
        speculatively re-issued when an idle worker exists; the per-ordinal
        ledger dedups whichever copy loses.
        """
        deadline = self._item_deadline_s
        hedge_s = (self._hedge_threshold()
                   if self._hedge_after is not None else None)
        if ((deadline is None and hedge_s is None)
                or self._heartbeats is None or not self._procs):
            return
        now = time.time()  # heartbeats are wall-clock (cross-process)
        idle = False
        busy = []
        for i, p in enumerate(self._procs):
            if not p.is_alive() or i in self._retired:
                continue
            hb_ordinal, since = self._read_heartbeat(i)
            if hb_ordinal == -1.0:
                if i not in self._retiring:  # an exiting slot can't hedge
                    idle = True
            else:
                busy.append((i, p, hb_ordinal, max(0.0, now - since)))
        for i, p, hb_ordinal, elapsed in busy:
            ordinal = int(hb_ordinal) if hb_ordinal >= 0 else None
            if self._is_delivering(i):
                # the worker finished its work function and is mid-enqueue:
                # SIGKILLing now could orphan the results channel's shared
                # write lock (held inside the worker's synchronous send)
                # and deadlock every other worker's put forever.  The
                # result is moments away; skip this sweep.  (The consumer only runs this sweep
                # while starving, so the pipe is drained and the delivery
                # window is short - not a loophole a truly hung worker can
                # hide in: a hang wedges INSIDE fn, before the flag flips.)
                continue
            if deadline is not None and elapsed > deadline:
                logger.warning(
                    "Worker process %d (pid %s) hung on item %s for %.1fs >"
                    " item_deadline_s=%.1f; SIGKILLing and respawning", i,
                    p.pid, ordinal if ordinal is not None else "?", elapsed,
                    deadline)
                if self._is_delivering(i):
                    continue  # flipped between the first check and the kill
                p.kill()
                p.join(timeout=10)
                # re-read AFTER death: the pre-kill sample may be stale (the
                # worker can have finished that item and started another
                # before the signal landed); the post-mortem heartbeat is the
                # authoritative crash ledger
                hb_ordinal, _since = self._read_heartbeat(i)
                ordinal = int(hb_ordinal) if hb_ordinal >= 0 else None
                self._heartbeats[3 * i + 1] = time.time()
                self._heartbeats[3 * i] = -1.0
                self._heartbeats[3 * i + 2] = -1.0
                self._hung_workers_killed += 1
                self._m_hung_killed.add(1)
                with self._resize_lock:
                    if i in self._retiring:
                        # the hung worker was already flagged to retire:
                        # killing it completes the retirement; do not
                        # respawn the slot
                        self._retiring.discard(i)
                        self._retired.add(i)
                    else:
                        # replace BEFORE the (possibly raising) requeue: the
                        # pool must keep its worker count whether or not the
                        # item has budget left
                        self._procs[i] = self._spawn_worker(i)
                self._requeue_lost(
                    ordinal, f"hung worker process {i} SIGKILLed after"
                    f" exceeding item deadline {deadline:.1f}s",
                    exhausted_kind="data")
                continue
            if (hedge_s is not None and elapsed > hedge_s and idle
                    and self._hedge(
                        ordinal, f"straggling {elapsed:.1f}s on worker"
                        f" process {i} (hedge threshold {hedge_s:.1f}s)")):
                idle = False  # one speculative copy per sweep

    def get(self, timeout: Optional[float] = None) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            try:
                result = self._out_queue.get(timeout=_POLL_S)
            except queue.Empty:
                self._service_faults()
                if deadline is not None and time.monotonic() > deadline:
                    raise
                active = [p for i, p in enumerate(self._procs)
                          if i not in self._retired]
                if active and not any(p.is_alive() for p in active):
                    if self._stop_on_failure:
                        self.stop()
                    raise WorkerError("All worker processes died (possible crash/OOM);"
                                      " no result will arrive", kind="infra")
                continue
            if isinstance(result, _Failure):
                if self._deliver_failure(result):
                    continue  # infra failure absorbed by a requeue
            ordinal, value = ((result.ordinal, result.value)
                              if isinstance(result, _Ok) else (None, result))
            settled = self._settle(ordinal)
            if self._arena is not None:
                from petastorm_tpu.native.transport import (decode_batch,
                                                            slot_column_count)

                if self._telemetry.enabled:
                    # parent-side proof of the zero-copy decode path: columns
                    # the worker decoded DIRECTLY into arena batch slots
                    # (child-process counters never reach this registry)
                    slots = slot_column_count(value)
                    if slots:
                        self._telemetry.counter("decode.batch_slots").add(slots)
                # decode duplicates too: the encoded descriptor pins arena
                # slots that only the decoded view's lifetime releases
                value = decode_batch(self._arena, value)
            if not settled:
                continue  # requeue duplicate: first delivery already counted
            self._note_delivery(ordinal, getattr(result, "attempt", 0))
            self._consumed += 1
            if self._telemetry.enabled:
                try:  # mp.Queue.qsize raises on some platforms
                    self._g_out_depth.set(self._out_queue.qsize())
                    self._g_in_depth.set(self._in_queue.qsize())
                except NotImplementedError:
                    pass
                self._harvest_full_waits()
            return value

    def _harvest_full_waits(self) -> None:
        """Fold the workers' accumulated blocked-on-full-channel seconds
        (shared ``_full_waits`` cells, written by ``_process_worker_main``'s
        ``out_queue.put``) into ``queue.results_full_wait_s`` as deltas, so
        the consumer-bound signal is visible to the sampler and the autotune
        controller despite the waits happening in child processes."""
        if self._full_waits is None:
            return
        total = sum(self._full_waits)
        delta = total - self._full_wait_harvested
        if delta > 0:
            self._full_wait_harvested = total
            self._m_results_full.add(delta)

    def stop(self) -> None:
        self._stopped = True
        self._stop_event.set()

    def join(self) -> None:
        if not self._stopped:
            raise PetastormTpuError("call stop() before join()")
        # close the results channel FIRST: a worker parked in a blocking
        # send (consumer abandoned mid-epoch with a large frame in flight)
        # gets EPIPE immediately and exits at its next stop_event check,
        # instead of burning the full 5s join timeout per worker
        self._out_queue.close()
        for p in self._procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        self._in_queue.cancel_join_thread()
        if self._arena is not None:
            # consumer-side batches may still hold zero-copy views; close()
            # defers the unmap until they are collected
            self._arena.close()

    @property
    def diagnostics(self) -> dict:
        if self._retire_flags is not None:
            self._promote_retirements()  # count acked shrinks sweep-free
        diag = {**super().diagnostics, "workers_count": self._workers_count,
                "workers_alive": sum(p.is_alive()
                                     for i, p in enumerate(self._procs)
                                     if i not in self._retired),
                "workers_retired": len(self._retired),
                "shm_transport": self._arena is not None}
        try:  # mp.Queue.qsize raises NotImplementedError on some platforms
            diag["in_queue_size"] = self._in_queue.qsize()
            diag["results_queue_size"] = self._out_queue.qsize()
        except NotImplementedError:
            pass
        if self._heartbeats is not None:
            now = time.time()
            busy = []
            for i in range(len(self._procs)):
                if i in self._retired:
                    continue
                # double-read-validated pair: a torn read can no longer pair
                # a new ordinal with a stale timestamp (bogus stall)
                ordinal, since = self._read_heartbeat(i)
                if ordinal != -1.0:  # -1 = idle; -2 = busy, ordinal unknown
                    # clamp: the worker may stamp a newer wall-clock time
                    # between our `now` snapshot and this read (and
                    # time.time() can step backwards under NTP)
                    busy.append((i, int(ordinal) if ordinal >= 0 else "?",
                                 round(max(0.0, now - since), 3)))
            diag["workers_busy"] = busy
        if self._arena is not None:
            diag["shm_free_bytes"] = self._arena.free_bytes()
        return diag


def parse_hedge_after(value: str):
    """CLI string -> ``hedge_after_s`` value: ``'auto'`` or a positive
    float.  Raises ValueError (argparse renders it as a usage error when
    used as a ``type=``) on anything else - shared by the throughput and
    diagnose CLIs."""
    if value == "auto":
        return "auto"
    try:
        parsed = float(value)
    except ValueError:
        raise ValueError(
            f"expected a number of seconds or 'auto', got {value!r}")
    if parsed <= 0:
        raise ValueError("hedge threshold must be > 0 seconds")
    return parsed


def make_executor(kind: str = "thread", workers_count: int = 3,
                  results_queue_size: int = DEFAULT_RESULTS_QUEUE_SIZE,
                  telemetry=None, stop_on_failure: bool = True,
                  max_requeue_attempts: int = DEFAULT_REQUEUE_ATTEMPTS,
                  item_deadline_s: Optional[float] = None,
                  hedge_after_s=None,
                  stall_warn_s: Optional[float] = None,
                  max_workers: Optional[int] = None) -> ExecutorBase:
    """'thread' | 'process' | 'serial' (reference: reader_pool_type, reader.py:139-150).

    ``stop_on_failure=False`` keeps the pool alive when a worker failure is
    delivered at ``get`` (the reader's ``on_error`` skip policies);
    ``max_requeue_attempts`` bounds the transparent re-ventilation of items
    lost to worker crashes.  ``item_deadline_s``/``hedge_after_s`` arm the
    liveness layer (hung-worker kill/abandon + straggler hedging; serial
    pools cannot enforce either - the work runs inline on the consumer).
    ``stall_warn_s`` reaches the serial pool's per-item watchdog (the one
    flavor whose mid-item stalls the reader-side loop cannot observe);
    thread/process pools take their stall thresholds from the reader.
    ``max_workers`` sizes the process pool's pre-allocated resize slot
    capacity (``resize_workers`` / petastorm_tpu.autotune can grow the pool
    up to it); thread pools grow without a pre-allocated ceiling.
    """
    if kind == "thread":
        return ThreadedExecutor(workers_count, results_queue_size,
                                telemetry=telemetry,
                                stop_on_failure=stop_on_failure,
                                max_requeue_attempts=max_requeue_attempts,
                                item_deadline_s=item_deadline_s,
                                hedge_after_s=hedge_after_s)
    if kind == "process":
        return _ProcessExecutor(workers_count, results_queue_size,
                                telemetry=telemetry,
                                stop_on_failure=stop_on_failure,
                                max_requeue_attempts=max_requeue_attempts,
                                item_deadline_s=item_deadline_s,
                                hedge_after_s=hedge_after_s,
                                max_workers=max_workers)
    if kind in ("serial", "dummy"):
        return SerialExecutor(telemetry=telemetry,
                              stop_on_failure=stop_on_failure,
                              max_requeue_attempts=max_requeue_attempts,
                              item_deadline_s=item_deadline_s,
                              hedge_after_s=hedge_after_s,
                              stall_warn_s=stall_warn_s)
    raise PetastormTpuError(f"Unknown executor kind {kind!r}")


class Ventilator:
    """Background thread feeding epoch work-items into an executor.

    Reference: ConcurrentVentilator (ventilator.py:55-166).  Backpressure comes
    from the executor's bounded input queue; per-epoch ordering comes from the
    deterministic ReadPlan, so this thread holds no shuffle state.
    """

    def __init__(self, executor: ExecutorBase, plan, num_epochs: Optional[int] = 1,
                 start_item: int = 0, telemetry=None,
                 release_window: Optional[int] = None,
                 release_progress=None):
        if num_epochs is not None and num_epochs < 1:
            raise PetastormTpuError("num_epochs must be >= 1 or None (infinite)")
        if start_item < 0:
            raise PetastormTpuError("start_item must be >= 0")
        self._executor = executor
        self._plan = plan
        self._num_epochs = num_epochs
        self._start_item = start_item
        # deterministic-delivery backpressure (docs/operations.md
        # "Reproducibility"): with a release window, ordinal v is not handed
        # to the executor until v < release_progress() + release_window.
        # The executor's queue bounds alone do NOT bound the reader's
        # reorder stage - a single straggling rowgroup frees its queue
        # slots to later items one by one while the reorder stage holds
        # every completed batch past it, so without this window the held
        # set could grow toward a whole epoch of decoded batches.  The
        # window must be at least the executor's in-flight capacity or it
        # would deadlock the very items the release is waiting on.
        self._release_window = release_window
        self._release_progress = release_progress
        self._telemetry = _resolve_telemetry(telemetry)
        if self._telemetry.enabled:
            # visible (as "no samples yet") in reports and --watch frames
            # even before the first item is handed to the executor
            register = getattr(self._telemetry, "register_stage", None)
            if register is not None:
                register("ventilate")
        self._stop_event = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.items_per_epoch = len(plan.epoch_items(0))
        #: absolute ordinal AFTER the last item actually handed to the
        #: executor (== items guaranteed to flow through to the consumer);
        #: exact once the thread is joined (see pause_and_join)
        self.ventilated = start_item

    @property
    def total_items(self) -> Optional[int]:
        """Items this ventilator will emit (excludes skipped resume prefix)."""
        if self._num_epochs is None:
            return None
        # plans know their own totals (ElasticResumePlan's leftover epoch is
        # shorter than its subsequent epochs)
        return max(self._plan.total_items(self._num_epochs) - self._start_item, 0)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, name="petastorm-tpu-ventilator",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        # resume: skip whole epochs cheaply, then a within-epoch offset
        if self.items_per_epoch > 0:
            epoch = self._start_item // self.items_per_epoch
            offset = self._start_item % self.items_per_epoch
        else:
            epoch, offset = 0, 0
        ordinal = self._start_item  # absolute position in the full item stream
        while not self._stop_event.is_set():
            if self._num_epochs is not None and epoch >= self._num_epochs:
                return
            tele = self._telemetry
            # same counter object the executor's put updates (same registry
            # name), and put runs in THIS thread - so the delta across one
            # put is exactly that put's queue-full wait
            m_blocked = tele.counter("queue.input_full_wait_s")
            for item in self._plan.epoch_items(epoch)[offset:]:
                if self._stop_event.is_set():
                    return
                if self._release_window is not None:
                    # deterministic-delivery window: never run more than one
                    # window ahead of the reader's release point (bounds the
                    # reorder stage's memory; see __init__)
                    while (ordinal >= self._release_progress()
                           + self._release_window):
                        if self._stop_event.wait(0.01):
                            return
                try:
                    if tele.enabled:
                        # ventilate busy time must EXCLUDE time blocked on a
                        # full input queue (tracked by the executor as
                        # queue.input_full_wait_s), or a consumer-bound
                        # pipeline would crown 'ventilate' the dominant stage
                        # for doing nothing but waiting
                        t0 = time.perf_counter_ns()
                        blocked0 = m_blocked.value
                        self._executor.put(VentilatedItem(ordinal, item),
                                           cancel_event=self._stop_event)
                        dur_ns = time.perf_counter_ns() - t0
                        blocked_ns = int((m_blocked.value - blocked0) * 1e9)
                        tele.record_stage("ventilate", t0,
                                          max(dur_ns - blocked_ns, 0),
                                          {"ordinal": ordinal})
                    else:
                        self._executor.put(VentilatedItem(ordinal, item),
                                           cancel_event=self._stop_event)
                except (ReaderClosedError, VentilationCancelled):
                    return
                ordinal += 1
                self.ventilated = ordinal
            offset = 0
            epoch += 1

    def stop(self) -> None:
        self._stop_event.set()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()

    def pause_and_join(self) -> int:
        """Stop issuing new work items and wait for the thread; returns the
        exact count of items ventilated (items already handed to the executor
        still flow through to the consumer - nothing is retracted).  The
        quiesce half of drain-to-cursor checkpointing (Reader.quiesce)."""
        self._stop_event.set()
        self.join()
        return self.ventilated
