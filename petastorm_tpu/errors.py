"""Exception types and failure-handling policy for petastorm_tpu.

Reference parity: petastorm/errors.py (NoDataAvailableError at errors.py:16-17).

Beyond the reference: the fault-tolerance layer (``make_reader(on_error=...)``)
lives here - the :class:`ErrorPolicy` knob, its budget-exhaustion error, and
the data-vs-infrastructure classification the pool applies to worker
failures.  A multi-hour pod epoch must not die on one poisoned jpeg in a
million rows (tf.data service treats skip-and-account semantics as a
prerequisite for production serving); equally, silently skipping half the
dataset must not look like success - hence explicit budgets.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


#: default infra-failure requeue budget (attempts beyond the first
#: delivery) - shared by every pool flavor and by ErrorPolicy, so skip-mode
#: and raise-mode readers can never drift apart.  Lives here (not pool.py)
#: because pool imports errors, not the reverse.
DEFAULT_REQUEUE_ATTEMPTS = 2


class PetastormTpuError(Exception):
    """Base class for all petastorm_tpu errors."""


class NoDataAvailableError(PetastormTpuError):
    """Raised when a reader shard/predicate/selector combination selects no data.

    Reference: petastorm/errors.py:16, raised at petastorm/reader.py:502-504 when
    there are fewer rowgroups than shards.
    """


class SchemaError(PetastormTpuError):
    """Schema definition, serialization, or validation failure."""


class CodecError(PetastormTpuError):
    """Codec encode/decode failure (bad dtype, non-compliant shape, ...)."""


class MetadataError(PetastormTpuError):
    """Dataset metadata is missing or unreadable (not a petastorm_tpu dataset)."""


class ReaderClosedError(PetastormTpuError):
    """Operation on a reader that has been stopped/joined."""


class EpochNotFinishedError(PetastormTpuError):
    """reset() called mid-epoch.

    Reference prohibits mid-epoch reset (petastorm/reader.py:438-445); we keep the
    same contract because in-flight work items would leak across epochs.
    """


class ErrorBudgetExceededError(PetastormTpuError):
    """An ``on_error`` skip policy ran out of budget.

    Raised by the reader when the number (or fraction) of skipped rowgroups
    exceeds the :class:`ErrorPolicy` limits - too many failures stop looking
    like weather and start looking like a broken dataset or outage, which
    must fail loudly rather than silently train on a shrinking sample.

    ``diagnostics``: the reader's pipeline-state snapshot taken at abort
    time (queue depths, quarantine ledger, and - when telemetry is on - the
    flight-recorder record with the sampled series leading into the
    exhaustion), same contract as
    :class:`~petastorm_tpu.pool.PipelineStallError`.
    """

    def __init__(self, message: str, diagnostics: Optional[dict] = None):
        super().__init__(message)
        self.diagnostics = diagnostics or {}


class CircuitOpenError(OSError, PetastormTpuError):
    """The storage circuit breaker is open: consecutive transient-IO
    failures crossed :class:`~petastorm_tpu.retry.RetryPolicy.circuit_threshold`
    and further IO is failed FAST instead of each worker independently
    burning its full retry-with-backoff budget against a store that is
    plainly down (a retry storm compounds an outage: N workers x
    max_attempts x backoff of traffic against a struggling backend).

    Subclasses ``OSError`` so the existing failure classification holds: the
    exhausted-retry classification (``classify_error`` -> ``'data'``)
    applies, meaning an ``on_error`` skip policy quarantines the affected
    rowgroups and a budgeted policy trips
    :class:`ErrorBudgetExceededError` during a sustained outage - while
    ``is_transient`` explicitly refuses to retry it (the breaker exists to
    STOP retries).  After ``circuit_cooldown_s`` one probe call is let
    through (half-open); success closes the circuit again.
    """


@dataclasses.dataclass(frozen=True)
class ErrorPolicy:
    """Skip-and-account failure policy for ``make_reader(on_error=...)``.

    With a policy in force, *data* errors (corrupt rowgroup, codec/transform
    failure - see :func:`classify_error`) no longer kill the read: the
    failing work item is skipped, quarantined in ``Reader.diagnostics``
    (``quarantined_rowgroups``) and counted in telemetry
    (``errors.skipped_rowgroups``), and iteration continues.  *Infrastructure*
    errors (worker process crash/OOM) are first requeued transparently onto
    surviving workers up to ``max_requeue_attempts``; only an item that
    exhausts its attempts is handed to the skip path.

    ``max_skipped_rowgroups``: absolute skip budget (None = unlimited).
    ``max_skipped_fraction``: skipped / expected items (None = unlimited);
    the denominator is the total expected item count, or - for
    ``num_epochs=None`` readers, which have no total - the items consumed
    so far, floored at one epoch (so a steady per-epoch corruption rate
    reads as a steady fraction, not a cumulative count).  Exceeding either
    raises :class:`ErrorBudgetExceededError`.
    """

    max_skipped_rowgroups: Optional[int] = None
    max_skipped_fraction: Optional[float] = None
    max_requeue_attempts: int = DEFAULT_REQUEUE_ATTEMPTS

    def __post_init__(self):
        if (self.max_skipped_rowgroups is not None
                and self.max_skipped_rowgroups < 0):
            raise PetastormTpuError(
                "ErrorPolicy.max_skipped_rowgroups must be >= 0 or None")
        if (self.max_skipped_fraction is not None
                and not 0.0 <= self.max_skipped_fraction <= 1.0):
            raise PetastormTpuError(
                "ErrorPolicy.max_skipped_fraction must be in [0, 1] or None")
        if self.max_requeue_attempts < 0:
            raise PetastormTpuError(
                "ErrorPolicy.max_requeue_attempts must be >= 0")


def resolve_error_policy(on_error) -> Optional[ErrorPolicy]:
    """User-facing ``on_error`` knob -> concrete policy (None = raise mode).

    ``'raise'``/None keeps today's fail-fast behavior; ``'skip'`` is an
    unbudgeted :class:`ErrorPolicy`; an ``ErrorPolicy`` passes through.
    """
    if on_error is None or on_error == "raise":
        return None
    if on_error == "skip":
        return ErrorPolicy()
    if isinstance(on_error, ErrorPolicy):
        return on_error
    raise PetastormTpuError(
        f"on_error must be 'raise', 'skip' or an ErrorPolicy; got {on_error!r}")


def classify_error(exc: BaseException) -> str:
    """Classify a worker failure: ``'data'`` (skip-eligible) vs ``'infra'``.

    Anything *raised inside* a worker function - CodecError, pyarrow
    ArrowInvalid, transform exceptions - is treated as a property of the
    work item and classifies as ``'data'``: retrying it on another worker
    would fail identically, so the only useful recovery is skip +
    quarantine.  ``'infra'`` failures are properties of the *worker* (OOM,
    crash): the item itself is healthy and requeues onto a surviving
    worker.  A worker process that dies without delivering a traceback is
    classified ``'infra'`` by the pool directly (it never reaches here).

    Deliberate edge: an IO error that already exhausted its ``io_retries``
    budget ALSO classifies as ``'data'`` - the bounded retry layer is the
    designated defense against weather, and reclassifying its failures as
    requeueable would double-retry every outage.  The consequence is that a
    sustained storage outage under an *unbudgeted* skip policy will skip
    (not fail) every rowgroup it touches; production skip policies should
    set ``ErrorPolicy`` budgets so an outage trips
    :class:`ErrorBudgetExceededError` instead of silently shrinking the
    sample (docs/operations.md "Failure handling").
    """
    if isinstance(exc, MemoryError):
        return "infra"
    return "data"
