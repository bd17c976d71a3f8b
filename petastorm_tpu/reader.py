"""Reader core: make_reader / make_batch_reader factories and the Reader iterator.

Reference parity: petastorm/reader.py (631 LoC) -
``make_reader`` (reader.py:59-176), ``make_batch_reader`` (reader.py:179-290),
``Reader.__init__`` pipeline (reader.py:344-351: open dataset -> load schema ->
view/transform -> list rowgroups -> filter by predicate/selector/shard -> ventilate
-> start pool), sharding (reader.py:492-509), partition-level predicate pushdown
(reader.py:532-563), selector filtering (reader.py:511-530), shuffle knobs
(reader.py:565-592), epoch iteration + reset-after-epoch-only (reader.py:423-447),
context manager stop/join (reader.py:594-631), diagnostics (reader.py:603-605).

Design differences (TPU-first):

* One columnar decode plane (petastorm_tpu/worker.py) serves both factories; the
  row/batch distinction is only how the iterator unpacks ColumnBatches.  The
  reference's per-row dict path (its main CPU bottleneck, SURVEY.md section 7) does
  not exist here.
* Deterministic seeded plans (petastorm_tpu/plan.py) make epochs reproducible and
  resumable: ``Reader.state_dict()`` captures a work-item cursor and
  ``make_reader(..., resume_from=state)`` restarts ventilation at that cursor -
  the checkpoint/resume gap called out in SURVEY.md section 5.  The cursor is
  exact at epoch boundaries; mid-epoch it is approximate by up to the in-flight
  window (workers complete items out of order), so pair it with a shuffle_seed
  and snapshot at step boundaries for deterministic training resumption.
* ``cur_shard``/``shard_count`` stay explicit here; ``petastorm_tpu.jax`` defaults
  them from the JAX process mesh (this module stays jax-free).
"""

from __future__ import annotations

import inspect
import logging
import os
import queue
import time
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from petastorm_tpu.batch import ColumnBatch
from petastorm_tpu.cache import make_cache
from petastorm_tpu.errors import (EpochNotFinishedError,
                                  ErrorBudgetExceededError, ErrorPolicy,
                                  MetadataError, NoDataAvailableError,
                                  PetastormTpuError, ReaderClosedError,
                                  resolve_error_policy)
from petastorm_tpu.etl.indexing import get_row_group_indexes
from petastorm_tpu.etl.metadata import open_dataset
from petastorm_tpu.fs import FilesystemFactory
from petastorm_tpu.plan import ElasticResumePlan, ReadPlan, elastic_resume_plan
from petastorm_tpu.pool import (DEFAULT_REQUEUE_ATTEMPTS, PipelineStallError,
                                Ventilator, WorkerError, _env_seconds,
                                make_executor)
from petastorm_tpu.schema import Schema
from petastorm_tpu.telemetry import NULL_CONTEXT as _NULL_CONTEXT
from petastorm_tpu.telemetry import dominant_stage
from petastorm_tpu.telemetry import resolve as _resolve_telemetry
from petastorm_tpu.transform import TransformSpec, transform_schema
from petastorm_tpu.worker import RowGroupDecoderWorker

logger = logging.getLogger(__name__)

_GET_TIMEOUT_S = 0.5
_DEFAULT_RESULTS_QUEUE_BATCHES = 10  # batches are whole rowgroups; keep RAM bounded
# stall detection (see Reader._next_batch)


# defaults; re-read from the environment at every Reader construction so
# setting the vars after `import petastorm_tpu` still takes effect
_STALL_WARN_S = 120.0
_STALL_ABORT_S = 0.0


def make_reader(dataset_url: str,
                schema_fields: Optional[Sequence] = None,
                reader_pool_type: str = "thread",
                workers_count: Union[int, str] = 4,
                results_queue_size: Optional[int] = None,
                shuffle_row_groups: bool = True,
                shuffle_row_drop_partitions: int = 1,
                shuffle_seed: Optional[int] = None,
                deterministic: Optional[str] = "auto",
                predicate=None,
                rowgroup_selector=None,
                num_epochs: Optional[int] = 1,
                cur_shard: Optional[int] = None,
                shard_count: Optional[int] = None,
                shard_mode: str = "static",
                cache_type: str = "null",
                cache_location: Optional[str] = None,
                cache_size_limit: Optional[int] = None,
                transform_spec: Optional[TransformSpec] = None,
                storage_options: Optional[dict] = None,
                filesystem=None,
                resume_from: Optional[dict] = None,
                verify_checksums: bool = False,
                decode_placement: Optional[Dict[str, str]] = None,
                decode_threads: Union[int, str] = "auto",
                decode_roi: Optional[Dict[str, tuple]] = None,
                ngram=None,
                io_retries="auto",
                telemetry=None,
                on_error="raise",
                item_deadline_s: Optional[float] = None,
                hedge_after_s=None,
                stall_warn_s: Optional[float] = None,
                stall_abort_s: Optional[float] = None,
                metrics_port: Optional[int] = None,
                flight_record_path: Optional[str] = None,
                sample_interval_s: Optional[float] = None,
                autotune=None,
                service_address=None,
                service_weight: Optional[float] = None,
                service_priority: Optional[int] = None,
                trace_items=None,
                chaos=None) -> "Reader":
    """Row-oriented reader for petastorm_tpu-created datasets (codec-decoded rows).

    Reference: ``make_reader`` (reader.py:59-176).  Yields one namedtuple row per
    ``next()``; for the TPU feed path prefer ``make_batch_reader`` +
    ``petastorm_tpu.jax`` (columnar, batched, device-sharded).

    ``decode_placement={'field': 'device'}`` routes a jpeg field's FLOP-heavy
    decode on-chip: the workers run only the entropy half and ship coefficient
    planes, which ONLY ``petastorm_tpu.jax.JaxDataLoader`` can finish - row
    iteration and the torch/tf adapters refuse such readers (they would see
    planes, not pixels).  ``'device'`` requires uniform jpeg geometry across
    the dataset (one XLA compile); ``'device-mixed'`` supports mixed
    geometries/subsamplings: the loader pads each image into the smallest
    of a fixed set of canvases (its ``pad_shapes``) and delivers each
    image's extent beside the batch, so compiles are bounded by chroma
    layouts x canvases x three group sizes, never by the number of
    distinct geometries.

    ``decode_placement={'field': 'auto'}`` makes the host<->device split a
    LIVE knob (docs/operations.md "Decode tuning"): workers consult a shared
    cell per rowgroup and ship either full host-decoded pixels or
    entropy-only coefficient planes; ``Reader.set_decode_split()`` moves it,
    and an armed autotune controller drives it from the queue-wait signals
    (the ``autotune.decode_split`` gauge carries the trajectory).  'auto'
    otherwise validates exactly like 'device' and also requires the
    JaxDataLoader.

    ``decode_threads``: internal fan-out of the native batched image decode
    inside EACH worker (the batch splits across a C++ thread pool with the
    GIL released).  ``'auto'`` (default) sizes it to this host's usable
    cores divided by the worker count, so a single-worker reader still
    decodes multi-core; an int pins it (1 restores the old per-worker
    single-thread decode).

    ``decode_roi``: partial image decode for augment-crop pipelines - decode
    only the pixels the crop keeps.  ``{'image': (y, x, h, w)}`` decodes a
    fixed window, ``('center', h, w)`` centers it, ``('random', h, w)``
    draws per-image offsets (deterministic per rowgroup, so requeue/resume
    re-reads decode identical crops).  Rows below the crop are never
    entropy-decoded; the delivered column (and the reader's output schema)
    has shape ``(h, w[, C])``.  Output is byte-identical to slicing a full
    decode.

    ``deterministic``: seed-stable delivery (docs/operations.md
    "Reproducibility").  ``'seed'`` inserts a bounded reorder stage between
    the executor and the consumer that releases batches in PLAN-ordinal
    order, so a (``shuffle_seed``, epoch) pair yields a bit-identical
    delivered stream - same visitation order, same batch boundaries -
    regardless of worker count, executor flavor (thread/process/serial),
    autotune resizes, chaos kills/requeues, hedge wins, and the
    ``service_address`` hop.  Every stochastic stage (plan permutation,
    shuffle buffers, weighted mixing, random decode crops) derives its RNG
    from one ``seeding.seed_stream`` root, and the reader maintains a
    running stream certificate - ``Reader.diagnostics['stream_digest']``,
    the ``stream.digest`` telemetry gauge, and ``state_dict()`` (a
    quiesce/resume split chains into the same combined digest as an
    uninterrupted run) - so two runs are diffed in O(1).  ``'off'`` delivers
    in completion order (faster first-batch latency; digests then certify
    only what THIS run delivered).  ``'auto'`` (default) = ``'seed'`` when a
    ``shuffle_seed`` is set, else ``'off'``.  In ``'seed'`` mode the
    autotune ``decode_split`` knob is excluded (a live host<->device flip
    depends on worker timing) and ``JaxDataLoader.straggler_release_s``
    no-ops (a release moves rows across batch boundaries between runs).

    ``cache_type``: decoded-rowgroup cache (docs/operations.md "Warm
    cache").  ``'null'`` (default) decodes every read; ``'memory'`` /
    ``'local-disk'`` are per-reader tiers (reference parity);
    ``'shared'`` is the HOST-WIDE warm tier (petastorm_tpu.cache_shared):
    decoded rowgroups live as columns in a shared-memory arena keyed by
    (dataset fingerprint, rowgroup, schema/transform/ROI/split signature),
    hit by every worker, epoch, reader and job on the host, backed by a
    bounded disk tier that survives restarts.  ``cache_location`` names the
    tier (same location = same tier host-wide) and the disk directory;
    ``cache_size_limit`` sizes the shared-memory arena.  Composes with the
    process pool and its zero-copy batch-slot decode; hit/miss/eviction
    rates ride the ``cache.*`` telemetry series, and an armed autotune
    controller trades cache memory against worker count live.  A
    ``transform_spec`` that is provably deterministic (declared via
    ``TransformSpec(deterministic=True)`` or concluded by the conservative
    ``'auto'`` bytecode + closure-constant analysis) additionally caches
    its OUTPUT under a stage-tagged key, so warm epochs skip decode AND
    transform (``cache.transform_hits`` / ``cache.transform_stores``
    counters; docs/operations.md "Transform caching & the pipeline
    planner").

    ``io_retries``: transient remote-IO policy (petastorm_tpu.retry).
    ``'auto'`` = bounded retry-with-backoff on remote filesystems (GCS/S3/
    HDFS/fsspec), off for local paths; an int sets the attempt budget; a
    ``RetryPolicy`` customizes backoff; ``None`` disables.

    ``telemetry``: pipeline observability (petastorm_tpu.telemetry).  The
    default is a zero-cost no-op recorder; pass a ``telemetry.Telemetry``
    (or ``True``) to record stage spans, queue waits and counters across the
    whole pipeline, or set ``PETASTORM_TPU_TELEMETRY=1`` to enable the
    process-wide recorder without touching code.  The resolved recorder is
    exposed as ``Reader.telemetry`` (``reader.telemetry.pipeline_report()``).

    ``on_error``: worker-failure policy (docs/operations.md "Failure
    handling").  ``'raise'`` (default) fails the read on the first worker
    failure - today's behavior.  ``'skip'`` quarantines rowgroups that fail
    with *data* errors (corrupt file, codec/transform exception) and keeps
    reading; an ``errors.ErrorPolicy`` adds budgets
    (``max_skipped_rowgroups`` / ``max_skipped_fraction``, exceeded ->
    ``ErrorBudgetExceededError``).  Independently of this knob,
    *infrastructure* failures (worker process crash/OOM) transparently
    requeue the lost work items onto surviving workers.  Skipped rowgroups
    are listed in ``Reader.diagnostics['quarantined_rowgroups']`` and
    counted in telemetry (``errors.skipped_rowgroups``).

    ``item_deadline_s``/``hedge_after_s``: the liveness layer
    (docs/operations.md "Liveness & stragglers").  With a deadline, an
    in-flight work item that produces no result for that long gets its
    worker SIGKILLed and respawned (process pool) or its slot abandoned
    (thread pool) and the item is requeued through the ``on_error`` requeue
    budget - a repeatedly-hanging item eventually quarantines as a data
    error.  ``hedge_after_s`` (seconds, or ``'auto'`` = 4x the telemetry
    decode p99) speculatively re-issues a straggling item to an idle
    worker; first result wins, the loser is deduplicated.  Both are
    inoperative on the serial pool (work runs inline on the consumer).
    Telemetry counts ``liveness.hung_workers_killed`` / ``.hedged_items`` /
    ``.hedge_wins``.

    ``stall_warn_s``/``stall_abort_s``: pipeline stall watchdog, previously
    env-only.  ``stall_warn_s`` (default 120) logs a WARNING naming the
    stuck workers when no result arrives for that long; ``stall_abort_s``
    (default off) escalates a longer stall to ``PipelineStallError``
    (diagnostics attached).  ``None`` falls back to
    ``PETASTORM_TPU_STALL_WARN_S`` / ``PETASTORM_TPU_STALL_ABORT_S``;
    ``0`` disables.

    ``metrics_port``/``flight_record_path``/``sample_interval_s``: the live
    observability layer (docs/operations.md "Live monitoring").  With
    telemetry enabled a background :class:`~petastorm_tpu.telemetry.sampler.
    MetricsSampler` continuously snapshots the registry (default every 1 s;
    ``sample_interval_s`` / ``PETASTORM_TPU_SAMPLE_INTERVAL_S`` tune it)
    into a bounded time-series ring (``reader.sampler``).  ``metrics_port``
    (or ``PETASTORM_TPU_METRICS_PORT``; ``0`` = ephemeral, read back via
    ``reader.metrics_server.port``) serves the metrics in Prometheus text
    format from a localhost-only HTTP thread.  ``flight_record_path`` (or
    ``PETASTORM_TPU_FLIGHT_RECORD``) dumps a flight record - the last ~60 s
    of sampled series plus the trace tail - as JSONL on any terminal failure
    (stall abort, terminal worker error, error-budget exhaustion,
    circuit-open abort); the record also lands in
    ``Reader.diagnostics['flight_recorder']``.  Passing any of the three
    KWARGS (a positive ``sample_interval_s`` counts - asking for a sampling
    cadence is asking to sample) auto-enables a private telemetry recorder
    when none is configured; the env vars for ``metrics_port`` and
    ``flight_record_path`` do too, but ``PETASTORM_TPU_SAMPLE_INTERVAL_S``
    alone only TUNES the cadence of telemetry that is otherwise enabled
    (a process-wide interval export must not silently switch recording on).

    ``autotune``: closed-loop pipeline autotuning (petastorm_tpu.autotune,
    docs/operations.md "Autotuning").  ``True`` (or an ``AutotunePolicy``)
    runs a background controller over the live metrics sampler that grows/
    shrinks the worker pool, resizes the results-queue bound and - once a
    ``JaxDataLoader`` wraps this reader - its prefetch depth, judging each
    move by delivered samples/s and reverting regressions.
    ``workers_count='auto'`` now implies it (pass ``autotune=False`` for
    the old static-only 'auto').  An armed policy also runs the STATIC
    pipeline planner first (petastorm_tpu.planner, unless
    ``AutotunePolicy(planner=False)``): parquet footer metadata plus the
    per-dataset flight profile recorded at previous readers' stop seed the
    starting workers / decode_threads / results-bound / prefetch /
    cache_mem, so the runtime loop only fine-tunes; the verdict with
    per-knob provenance is ``Reader.diagnostics['planner']`` and renders
    as a ``planner:`` line in ``diagnose --watch``.
    Auto-enables telemetry + the sampler; inoperative on the serial pool.
    Every decision is visible as ``autotune.*`` counters/gauges, trace
    events, and ``Reader.diagnostics['autotune']``.

    ``service_address``: consume through the disaggregated ingest service
    (docs/operations.md "Disaggregated ingest service") instead of an
    in-process pool.  ``'host:port'`` (or ``(host, port)``) of a running
    ``petastorm-tpu-service dispatcher``; the reader ships its worker
    factory to the dispatcher's remote-worker fleet and receives decoded
    batches over the wire - preprocessing then scales independently of
    this process, and co-located workers using ``cache_type='shared'``
    decode each rowgroup once across ALL clients of the dataset.  The
    deterministic plan, resume cursors, shuffle and ``on_error`` policies
    all behave exactly as with a local pool; ``reader_pool_type`` /
    ``workers_count`` are ignored (fleet size is the dispatcher's concern -
    its ``scaling_signal`` says when to grow it), and the liveness/autotune
    knobs that steer a local pool are inoperative client-side.  A lost
    dispatcher connection reconnects with backoff and, failing that,
    raises a classified infrastructure
    :class:`~petastorm_tpu.service.client.ServiceConnectionError` instead
    of hanging the epoch.

    ``service_weight`` / ``service_priority``: this trainer's multi-tenant
    QoS identity at the dispatcher (weighted deficit-round-robin share
    within a strict priority tier; docs/operations.md "Fleet autoscaling &
    QoS").  Defaults 1.0 / 0 (or ``$PETASTORM_TPU_SERVICE_WEIGHT`` /
    ``$PETASTORM_TPU_SERVICE_PRIORITY``); require ``service_address``.

    ``trace_items``: per-item distributed tracing on the service plane
    (default off; ``True`` = 1-in-16 sampling, int N = 1-in-N, env
    ``$PETASTORM_TPU_TRACE_ITEMS``).  Sampled items carry a trace context
    through every hop; the merged cross-process timeline lands in this
    reader's trace buffer (``Reader.telemetry.export_chrome_trace()`` ->
    one Perfetto file spanning client/dispatcher/workers) and feeds the
    ``service.hop.*`` latency-decomposition histograms.  Requires
    ``service_address`` (docs/operations.md "Distributed tracing & fleet
    view").

    ``chaos``: deterministic fault injection for tests/benchmarks
    (``petastorm_tpu.test_util.chaos.ChaosSpec``); never set in production.
    """
    return _make_reader_impl(dataset_url, schema_fields, reader_pool_type,
                             workers_count, results_queue_size, shuffle_row_groups,
                             shuffle_row_drop_partitions, shuffle_seed, predicate,
                             rowgroup_selector, num_epochs, cur_shard, shard_count,
                             shard_mode, cache_type, cache_location, cache_size_limit,
                             transform_spec, storage_options, filesystem,
                             batched_output=False, require_stored_schema=True,
                             deterministic=deterministic,
                             resume_from=resume_from, ngram=ngram,
                             verify_checksums=verify_checksums,
                             decode_placement=decode_placement,
                             decode_threads=decode_threads,
                             decode_roi=decode_roi,
                             io_retries=io_retries, telemetry=telemetry,
                             on_error=on_error, chaos=chaos,
                             item_deadline_s=item_deadline_s,
                             hedge_after_s=hedge_after_s,
                             stall_warn_s=stall_warn_s,
                             stall_abort_s=stall_abort_s,
                             metrics_port=metrics_port,
                             flight_record_path=flight_record_path,
                             sample_interval_s=sample_interval_s,
                             autotune=autotune,
                             service_address=service_address,
                             service_weight=service_weight,
                             service_priority=service_priority,
                             trace_items=trace_items)


def elastic_resume(states: Sequence[dict]) -> dict:
    """``resume_from`` token for resuming under a DIFFERENT shard layout.

    ``states``: EVERY old shard's ``Reader.state_dict()``, ordered by old
    shard index (a global checkpoint has all of them).  Pass the token to
    ``make_reader(..., resume_from=elastic_resume(states), cur_shard=<new>,
    shard_count=<new>, num_epochs=<epochs remaining, counting the partial
    one>)`` on every new host, with all other plan settings (seed, shuffle,
    drop partitions, shard_mode, filters) unchanged from the checkpointed
    run.  The leftover of the in-progress epoch is re-dealt across the new
    shards deterministically; no item is lost, and at most the old in-flight
    window is re-read (exact when checkpointed at an epoch boundary).

    An elastically-resumed reader checkpoints again like any other: its
    cursor records the rebased-coordinate translation and resumes plainly or
    elastically once past the leftover epoch.  A mid-leftover cursor is not
    expressible in per-shard coordinates and is refused with a clear error -
    checkpoint again after the leftover epoch finishes.

    Reference gap: "no elastic re-sharding, no mid-epoch resume"
    (SURVEY.md section 5).
    """
    return {"elastic": {"states": [dict(s) for s in states]}}


def make_batch_reader(dataset_url_or_urls: Union[str, Sequence[str]],
                      schema_fields: Optional[Sequence] = None,
                      reader_pool_type: str = "thread",
                      workers_count: Union[int, str] = 4,
                      results_queue_size: Optional[int] = None,
                      shuffle_row_groups: bool = True,
                      shuffle_row_drop_partitions: int = 1,
                      shuffle_seed: Optional[int] = None,
                      deterministic: Optional[str] = "auto",
                      predicate=None,
                      rowgroup_selector=None,
                      num_epochs: Optional[int] = 1,
                      cur_shard: Optional[int] = None,
                      shard_count: Optional[int] = None,
                      shard_mode: str = "static",
                      cache_type: str = "null",
                      cache_location: Optional[str] = None,
                      cache_size_limit: Optional[int] = None,
                      transform_spec: Optional[TransformSpec] = None,
                      storage_options: Optional[dict] = None,
                      filesystem=None,
                      resume_from: Optional[dict] = None,
                      verify_checksums: bool = False,
                      decode_placement: Optional[Dict[str, str]] = None,
                      decode_threads: Union[int, str] = "auto",
                      decode_roi: Optional[Dict[str, tuple]] = None,
                      ngram=None,
                      io_retries="auto",
                      telemetry=None,
                      on_error="raise",
                      item_deadline_s: Optional[float] = None,
                      hedge_after_s=None,
                      stall_warn_s: Optional[float] = None,
                      stall_abort_s: Optional[float] = None,
                      metrics_port: Optional[int] = None,
                      flight_record_path: Optional[str] = None,
                      sample_interval_s: Optional[float] = None,
                      autotune=None,
                      service_address=None,
                      service_weight: Optional[float] = None,
                      service_priority: Optional[int] = None,
                      trace_items=None,
                      chaos=None) -> "Reader":
    """Columnar batch reader for arbitrary parquet stores (schema inferred when no
    petastorm_tpu metadata exists).

    Reference: ``make_batch_reader`` (reader.py:179-290).  Yields one namedtuple of
    column arrays per decoded rowgroup.  ``deterministic``/``io_retries``/``telemetry``/
    ``on_error``/``item_deadline_s``/``hedge_after_s``/``stall_warn_s``/
    ``stall_abort_s``/``metrics_port``/``flight_record_path``/
    ``sample_interval_s``/``autotune``/``service_address``/
    ``service_weight``/``service_priority``/``trace_items``/``chaos``: see
    ``make_reader``.
    """
    return _make_reader_impl(dataset_url_or_urls, schema_fields, reader_pool_type,
                             workers_count, results_queue_size, shuffle_row_groups,
                             shuffle_row_drop_partitions, shuffle_seed, predicate,
                             rowgroup_selector, num_epochs, cur_shard, shard_count,
                             shard_mode, cache_type, cache_location, cache_size_limit,
                             transform_spec, storage_options, filesystem,
                             batched_output=True, require_stored_schema=False,
                             deterministic=deterministic,
                             resume_from=resume_from, ngram=ngram,
                             verify_checksums=verify_checksums,
                             decode_placement=decode_placement,
                             decode_threads=decode_threads,
                             decode_roi=decode_roi,
                             io_retries=io_retries, telemetry=telemetry,
                             on_error=on_error, chaos=chaos,
                             item_deadline_s=item_deadline_s,
                             hedge_after_s=hedge_after_s,
                             stall_warn_s=stall_warn_s,
                             stall_abort_s=stall_abort_s,
                             metrics_port=metrics_port,
                             flight_record_path=flight_record_path,
                             sample_interval_s=sample_interval_s,
                             autotune=autotune,
                             service_address=service_address,
                             service_weight=service_weight,
                             service_priority=service_priority,
                             trace_items=trace_items)


def _make_reader_impl(dataset_url, schema_fields, reader_pool_type, workers_count,
                      results_queue_size, shuffle_row_groups,
                      shuffle_row_drop_partitions, shuffle_seed, predicate,
                      rowgroup_selector, num_epochs, cur_shard, shard_count,
                      shard_mode, cache_type, cache_location, cache_size_limit,
                      transform_spec, storage_options, filesystem,
                      batched_output, require_stored_schema,
                      deterministic: Optional[str] = "auto",
                      resume_from: Optional[dict] = None, ngram=None,
                      verify_checksums: bool = False,
                      decode_placement: Optional[Dict[str, str]] = None,
                      decode_threads="auto",
                      decode_roi: Optional[Dict[str, tuple]] = None,
                      io_retries="auto", telemetry=None,
                      on_error="raise", chaos=None,
                      item_deadline_s: Optional[float] = None,
                      hedge_after_s=None,
                      stall_warn_s: Optional[float] = None,
                      stall_abort_s: Optional[float] = None,
                      metrics_port: Optional[int] = None,
                      flight_record_path: Optional[str] = None,
                      sample_interval_s: Optional[float] = None,
                      autotune=None,
                      service_address=None,
                      service_weight: Optional[float] = None,
                      service_priority: Optional[int] = None,
                      trace_items=None) -> "Reader":
    from petastorm_tpu.autotune import resolve_autotune
    from petastorm_tpu.seeding import resolve_deterministic

    telemetry = _resolve_telemetry(telemetry)
    deterministic = resolve_deterministic(deterministic, shuffle_seed)
    # None = default bound (10); an EXPLICIT int - even 10 - is pinned and
    # the planner never overrides it (a plain `= 10` default could not
    # distinguish "user asked for 10" from "user said nothing")
    results_queue_pinned = results_queue_size is not None
    if results_queue_size is None:
        results_queue_size = _DEFAULT_RESULTS_QUEUE_BATCHES
    # ONE transform-analysis walk per reader (it md5s bytecode + captured
    # arrays): the planner's schema hash and the worker's cache signature /
    # output-caching verdict all derive from this triple
    from petastorm_tpu.transform import transform_cache_info

    tf_cache_info = transform_cache_info(transform_spec)
    autotune_policy = resolve_autotune(autotune, workers_count,
                                       reader_pool_type)
    if deterministic == "seed" and autotune_policy is not None \
            and "decode_split" not in autotune_policy.exclude_knobs:
        # resizes/queue-bound/prefetch moves only change TIMING (the reorder
        # stage absorbs those), but a live host<->device decode-split flip
        # changes which wire form each rowgroup ships based on when a worker
        # decoded it - content no reorder stage can make seed-stable.
        # Exclude that one knob; everything else keeps tuning.
        import dataclasses as _dc

        autotune_policy = _dc.replace(
            autotune_policy,
            exclude_knobs=autotune_policy.exclude_knobs | {"decode_split"})
    if service_address is not None:
        if autotune_policy is not None:
            # the client has no local worker plane to resize; fleet sizing
            # is the dispatcher's scaling signal (docs/operations.md)
            if autotune is not None and autotune is not False:
                logger.warning(
                    "autotune is inoperative with service_address readers:"
                    " the worker plane lives in the remote fleet (size it"
                    " off the dispatcher's scaling_signal)")
            autotune_policy = None
        if item_deadline_s is not None or hedge_after_s is not None:
            logger.warning(
                "item_deadline_s/hedge_after_s are client-side liveness"
                " knobs and are inoperative with service_address readers"
                " (the dispatcher requeues items off dead workers)")
            item_deadline_s = hedge_after_s = None
        if cache_type == "memory":
            raise PetastormTpuError(
                "cache_type='memory' is process-local: every remote worker"
                " would hold its own empty cache. Use cache_type='shared'"
                " (the host-wide tier remote workers share) or"
                " 'local-disk' with service_address readers.")
    elif service_weight is not None or service_priority is not None:
        raise PetastormTpuError(
            "service_weight/service_priority are multi-tenant QoS knobs of"
            " the ingest service and need service_address (a local pool"
            " serves exactly one consumer - there is nothing to share)")
    elif trace_items:
        raise PetastormTpuError(
            "trace_items arms DISTRIBUTED per-item tracing across the"
            " ingest service's processes and needs service_address; local"
            " pools already trace every stage span into the telemetry"
            " trace buffer")
    if not flight_record_path:
        flight_record_path = (
            os.environ.get("PETASTORM_TPU_FLIGHT_RECORD", "").strip() or None)
    if metrics_port is None:
        raw_port = os.environ.get("PETASTORM_TPU_METRICS_PORT", "").strip()
        if raw_port:
            try:
                metrics_port = int(raw_port)
            except ValueError:
                logger.warning("Ignoring non-integer"
                               " PETASTORM_TPU_METRICS_PORT=%r", raw_port)
    if (flight_record_path or metrics_port is not None
            or autotune_policy is not None or trace_items
            or (sample_interval_s is not None and sample_interval_s > 0)) \
            and not telemetry.enabled:
        # the continuous-observability knobs (and the autotune loop, which
        # decides from the sampler's series) need a live recorder; a private
        # one keeps them usable without opting the whole process in
        from petastorm_tpu.telemetry import Telemetry

        telemetry = Telemetry()
    if telemetry.enabled:
        # pre-register the canonical stages this pipeline will run, so early
        # sampler frames and short runs render them as "no samples yet"
        # instead of omitting them (report.py)
        register = getattr(telemetry, "register_stage", None)
        if register is not None:
            register("decode")
            if transform_spec is not None:
                register("transform")
            if service_address is not None:
                # the service plane's client-side stage: a just-started
                # fleet renders as "(no samples yet)" in reports/--watch
                # instead of vanishing (docs/operations.md)
                register("service")
    error_policy = resolve_error_policy(on_error)
    if chaos is not None and chaos.affects_filesystem():
        # transient-IO chaos lives in the filesystem layer so it exercises
        # the REAL retry paths (worker rowgroup reads and metadata opens);
        # the wrapped fs is a non-local PyFileSystem, so io_retries='auto'
        # arms exactly as it would against GCS/S3
        from petastorm_tpu.fs import get_filesystem_and_path

        base_fs, _ = get_filesystem_and_path(
            dataset_url if isinstance(dataset_url, str) else dataset_url[0],
            storage_options, filesystem)
        filesystem = chaos.wrap_filesystem(base_fs)
    if ngram is not None and batched_output:
        raise PetastormTpuError(
            "NGram is not supported by make_batch_reader (reference parity,"
            " arrow_reader_worker.py:104); use make_reader")
    if ngram is not None and schema_fields is not None:
        raise PetastormTpuError(
            "schema_fields and ngram are mutually exclusive: the NGram spec"
            " already defines the fields read at each timestep offset")
    if (ngram is not None and predicate is not None
            and shuffle_row_drop_partitions > 1):
        raise PetastormTpuError(
            "ngram + predicate + shuffle_row_drop_partitions > 1 is not"
            " supported: the lookahead rows borrowed across a partition"
            " boundary are computed before the predicate masks rows, so"
            " windows spanning masked rows would be silently lost. Use"
            " shuffle_row_drop_partitions=1.")
    if cache_type == "memory" and reader_pool_type == "process":
        raise PetastormTpuError(
            "cache_type='memory' is process-local: every spawned worker would"
            " hold its own empty cache, giving zero hits while multiplying"
            " memory. Use reader_pool_type='thread' (the cache is shared and"
            " thread-safe) or cache_type='local-disk' with the process pool.")
    try:
        info = open_dataset(dataset_url, storage_options=storage_options,
                            filesystem=filesystem,
                            require_stored_schema=require_stored_schema,
                            io_retries=io_retries, telemetry=telemetry)
    except MetadataError as exc:
        if require_stored_schema:
            raise MetadataError(
                f"{exc}  (make_reader requires a petastorm_tpu dataset; for plain"
                " parquet use make_batch_reader)") from exc
        raise

    from petastorm_tpu.etl.metadata import infer_or_load_schema

    full_schema = infer_or_load_schema(info)
    view = full_schema.view(schema_fields) if schema_fields is not None else full_schema
    if decode_roi:
        _validate_decode_roi(decode_roi, full_schema,
                             [f.name for f in view], decode_placement, ngram)
        # the delivered columns are crop-shaped; the WORKER keeps the full
        # schema (it needs the stored geometry to place the crops)
        view = _apply_roi_schema(view, decode_roi)
    output_schema = (transform_schema(view, transform_spec)
                     if transform_spec is not None else view)
    ngram_schema = None
    if ngram is not None:
        # ngram defines its own field selection across the post-transform schema
        ngram_schema = (transform_schema(full_schema, transform_spec)
                        if transform_spec is not None else full_schema)
        required = ngram.required_fields(ngram_schema)
        # transform-created fields are not stored; read only what exists on disk
        view = full_schema.view([n for n in required if n in full_schema])
        output_schema = ngram_schema

    row_groups = info.row_groups
    # selector filter (reference reader.py:511-530)
    if rowgroup_selector is not None:
        indexes = get_row_group_indexes(info)
        selected = rowgroup_selector.select_row_groups(indexes)
        row_groups = [rg for rg in row_groups if rg.global_index in selected]
        if not row_groups:
            raise NoDataAvailableError("Rowgroup selector selected no rowgroups")
    # partition-level predicate pushdown (reference reader.py:532-563)
    worker_predicate = predicate
    if predicate is not None:
        pred_fields = set(predicate.get_fields())
        pkeys = set(info.partition_keys)
        if pred_fields and pred_fields <= pkeys:
            kept = []
            for rg in row_groups:
                pvals = dict(rg.partition_values)
                cols = {}
                for f in pred_fields:
                    # hive path values are strings; restore the field's dtype so
                    # the predicate sees the same types the worker path would
                    value = pvals[f]
                    field = full_schema[f] if f in full_schema else None
                    if field is not None and field.dtype.kind not in ("U", "S", "O"):
                        value = field.dtype.type(value)
                    cols[f] = np.asarray([value], dtype=object)
                if bool(predicate.do_include_vectorized(cols)[0]):
                    kept.append(rg)
            row_groups = kept
            worker_predicate = None
            if not row_groups:
                raise NoDataAvailableError("Predicate filtered out all partitions")

    if resume_from is not None and "elastic" in resume_from:
        # resume a partially-consumed epoch under a NEW shard layout: the old
        # shards' cursors fully determine the leftover items (plans are pure
        # functions of seed/epoch/shard). All OTHER settings (dataset,
        # predicate/selector filters, seed, shuffle, drop, shard_mode) must
        # match the checkpointed run.
        plan = elastic_resume_plan(
            row_groups, resume_from["elastic"]["states"],
            new_shard_index=cur_shard if cur_shard is not None else 0,
            new_shard_count=shard_count if shard_count is not None else 1,
            shuffle_row_groups=shuffle_row_groups, shuffle_seed=shuffle_seed,
            shuffle_row_drop_partitions=shuffle_row_drop_partitions,
            shard_mode=shard_mode)
    else:
        plan = ReadPlan(row_groups, shard_index=cur_shard, shard_count=shard_count,
                        shuffle_row_groups=shuffle_row_groups, shuffle_seed=shuffle_seed,
                        shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                        shard_mode=shard_mode)

    # cache+predicate is disallowed (reference py_dict_reader_worker.py:145-150);
    # cache+row-drop is fine here because cache keys include the row slice.
    # Refuse BEFORE make_cache: a 'shared' cache creates host-wide shm
    # segments + disk dirs at construction, which a raised refusal would leak
    if cache_type not in (None, "null", "none") and worker_predicate is not None:
        raise PetastormTpuError("cache_type cannot be combined with a predicate")
    cache = make_cache(cache_type, cache_location, cache_size_limit,
                       telemetry=telemetry)

    read_fields = [f.name for f in view]
    fs_factory = FilesystemFactory(dataset_url if isinstance(dataset_url, str)
                                   else dataset_url[0], storage_options,
                                   filesystem=filesystem)
    device_fields, mixed_fields, split_fields = _validate_decode_placement(
        decode_placement, full_schema, read_fields, transform_spec,
        ngram, worker_predicate)
    decode_split_cell = None
    if split_fields:
        # the live host<->device decode split: one shared int cell every
        # worker consults per rowgroup (0 = host pixels, 1 = device planes).
        # A spawn-context RawValue crosses the process-pool boundary through
        # Process args (same mechanism as the heartbeat arrays); thread and
        # serial pools just share the object.  Starts on the device side -
        # the hybrid split is the measured win when a chip is present - and
        # the autotune loop (or set_decode_split) moves it from there.
        import multiprocessing as _mp

        decode_split_cell = _mp.get_context("spawn").Value("i", 1, lock=False)
    from petastorm_tpu.retry import make_circuit_breaker, resolve_retry_policy

    retry_policy = resolve_retry_policy(io_retries, info.filesystem)
    # one breaker shared by every worker of this reader (thread pools share
    # the instance; spawned process workers unpickle per-process copies) -
    # a storage outage fails fast with CircuitOpenError instead of every
    # worker independently burning its full retry budget
    circuit_breaker = make_circuit_breaker(retry_policy)
    try:
        # usable cores (cgroup/affinity-aware), shared by both 'auto'
        # resolutions below
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    planner_verdict = None
    if (autotune_policy is not None and service_address is None
            and getattr(autotune_policy, "planner", True)):
        # the static planner pass (petastorm_tpu.planner): parquet footer
        # metadata + the recorded per-dataset flight profile seed the knobs
        # the runtime autotune loop starts from, so a cold start begins near
        # the optimum instead of exploring from static defaults.  Verdict +
        # per-knob provenance land in Reader.diagnostics['planner'].
        from petastorm_tpu import planner as _planner
        from petastorm_tpu.codecs import CompressedImageCodec

        try:
            planner_verdict = _planner.plan_reader(
                info, read_fields, policy=autotune_policy, cores=cores,
                cache_type=cache_type, cache_location=cache_location,
                transform_signature=tf_cache_info[0],
                split_fields=split_fields,
                workers_count=workers_count, decode_threads=decode_threads,
                results_queue_size=results_queue_size,
                results_queue_pinned=results_queue_pinned,
                image_fields=[f.name for f in view
                              if isinstance(f.codec, CompressedImageCodec)])
        except Exception:  # noqa: BLE001 - planning must not fail the read
            logger.warning("pipeline planner failed; starting from static"
                           " defaults", exc_info=True)
    if planner_verdict is not None:
        planned = planner_verdict.knobs
        if workers_count == "auto" and "workers" in planned:
            workers_count = planned["workers"].value
        if decode_threads == "auto" and "decode_threads" in planned:
            decode_threads = planned["decode_threads"].value
        if ("results_queue" in planned
                and planned["results_queue"].source in ("profile",
                                                        "metadata")):
            results_queue_size = planned["results_queue"].value
        if ("decode_split" in planned and decode_split_cell is not None
                and "decode_split" not in autotune_policy.exclude_knobs):
            # profile-recorded converged split side: start there instead of
            # the static device-side default.  NEVER under
            # deterministic='seed' (which puts 'decode_split' in
            # exclude_knobs): the split changes delivered CONTENT, and a
            # seed-stable run must not depend on hidden on-disk profile
            # state - two hosts with different profiles would certify
            # different streams for the same command
            decode_split_cell.value = planned["decode_split"].value
    if workers_count == "auto":
        # resolved here (it used to happen just before make_executor) so
        # decode_threads='auto' below can size against the real pool width:
        # one core left for the consumer, capped at the reference's default
        # pool size of 10
        workers_count = max(1, min(10, cores - 1))
    if decode_threads == "auto":
        # each worker's share of the usable cores: a 1-worker reader decodes
        # with every core, a saturated pool keeps 1 thread per worker (the
        # pool is then the parallelism) - multi-core decode end to end either
        # way (PAPERS.md: single-threaded decode baselines mis-evaluate
        # loaders; so would a single-threaded decode plane)
        decode_threads = max(1, cores // max(1, int(workers_count)))
    worker = RowGroupDecoderWorker(fs_factory, full_schema, read_fields,
                                   predicate=worker_predicate,
                                   transform=transform_spec, cache=cache,
                                   ngram=ngram, ngram_schema=ngram_schema,
                                   verify_checksums=verify_checksums,
                                   raw_fields=device_fields,
                                   mixed_raw_fields=mixed_fields,
                                   retry_policy=retry_policy,
                                   circuit_breaker=circuit_breaker,
                                   telemetry=telemetry,
                                   decode_threads=int(decode_threads),
                                   decode_roi=decode_roi,
                                   split_fields=split_fields,
                                   decode_split=decode_split_cell,
                                   transform_cache_info=tf_cache_info)
    if chaos is not None and chaos.affects_worker():
        from petastorm_tpu.test_util.chaos import ChaosWorker

        worker = ChaosWorker(worker, chaos)

    if service_address is not None:
        # the disaggregated service plane: the dispatcher's remote-worker
        # fleet replaces the in-process pool; the client executor speaks
        # the same ExecutorBase protocol so everything downstream (ledger,
        # resume cursor, on_error policies) is unchanged
        from petastorm_tpu.service.client import ServiceExecutor

        executor = ServiceExecutor(
            service_address, telemetry=telemetry,
            stop_on_failure=error_policy is None,
            max_requeue_attempts=(error_policy.max_requeue_attempts
                                  if error_policy is not None
                                  else DEFAULT_REQUEUE_ATTEMPTS),
            # the in-flight window is the service analog of the results
            # queue bound: batches outstanding at the dispatcher per client
            window=max(4, int(results_queue_size)),
            # multi-tenant QoS identity (weighted fair assignment + strict
            # priority tiers dispatcher-side); None = env/default
            weight=service_weight, priority=service_priority,
            # per-item distributed tracing (default off; None = env)
            trace_items=trace_items)
    else:
        executor = make_executor(
            reader_pool_type, workers_count, results_queue_size,
            telemetry=telemetry,
            # skip policies need the pool to survive delivered failures so
            # the consumer can quarantine the item and keep iterating
            stop_on_failure=error_policy is None,
            max_requeue_attempts=(error_policy.max_requeue_attempts
                                  if error_policy is not None
                                  else DEFAULT_REQUEUE_ATTEMPTS),
            item_deadline_s=item_deadline_s,
            hedge_after_s=hedge_after_s,
            # the serial pool's per-item watchdog is the only observer of a
            # mid-item stall there; it must honor the first-class kwarg too
            stall_warn_s=stall_warn_s,
            # process pools pre-allocate resize slots up to the autotune
            # ceiling
            max_workers=(autotune_policy.max_workers
                         if autotune_policy is not None else None))
    start_item = 0
    digest_state = None
    if resume_from is not None and "elastic" not in resume_from:
        # continue the stream-certificate chain across the split: the
        # resumed run's combined digest then equals an uninterrupted run's
        # (elastic resume re-deals several old shards' leftovers - their
        # per-shard chains cannot merge, so the new reader starts a fresh
        # chain)
        digest_state = resume_from.get("stream_digest")
        if "elastic_rebased" in resume_from:
            # cursor from an elastically-resumed reader: translate its rebased
            # coordinates back to this (base) plan's absolute item stream
            from petastorm_tpu.plan import resolve_cursor

            start_item, base_ipe = resolve_cursor(resume_from)
            plan_ipe = len(plan.epoch_items(0))
            if plan_ipe != base_ipe:
                raise PetastormTpuError(
                    f"cursor was taken under a layout with {base_ipe}"
                    f" items/epoch but this reader's plan has {plan_ipe};"
                    " shard count or plan settings differ - use"
                    " elastic_resume() with every shard's state instead")
        else:
            start_item = int(resume_from.get("position", 0))
    reader = Reader(info=info, schema=output_schema, plan=plan, executor=executor,
                    worker=worker, num_epochs=num_epochs, batched_output=batched_output,
                    start_item=start_item, ngram=ngram, telemetry=telemetry,
                    error_policy=error_policy, stall_warn_s=stall_warn_s,
                    stall_abort_s=stall_abort_s, metrics_port=metrics_port,
                    flight_record_path=flight_record_path,
                    sample_interval_s=sample_interval_s,
                    autotune_policy=autotune_policy,
                    deterministic=deterministic, shuffle_seed=shuffle_seed,
                    digest_state=digest_state)
    reader.circuit_breaker = circuit_breaker
    #: fields the jax loader decodes on-chip (raw jpeg bytes in host batches)
    reader.device_decode_fields = device_fields
    #: subset using the mixed-geometry object wire format ('device-mixed')
    reader.device_decode_mixed = mixed_fields
    #: subset under the LIVE host<->device split (decode_placement='auto'):
    #: their batches carry EITHER pixels or coefficient planes, per the
    #: split cell's value when the rowgroup decoded
    reader.device_decode_split = split_fields
    reader._decode_split_cell = decode_split_cell
    #: the static planner's verdict (petastorm_tpu.planner.PlanVerdict;
    #: None when the planner did not run) - knob provenance in
    #: diagnostics['planner'], flight profile written at stop()
    reader.planner = planner_verdict
    from petastorm_tpu.cache_shared import SharedWarmCache

    if isinstance(cache, SharedWarmCache):
        # the reader is the tier's telemetry publisher (cache.* series) and
        # surfaces tier stats in diagnostics; the tier itself is host-wide
        reader.warm_cache = cache
        if (planner_verdict is not None
                and "cache_mem" in planner_verdict.knobs
                and cache.l1_enabled
                and cache.get_target_bytes() == int(0.8 * cache.l1_size_bytes)
                and cache.stats().get("bytes", 0)
                <= planner_verdict.knobs["cache_mem"].value * 2 ** 20):
            # seed the L1 residency target ONLY while it still sits at its
            # creation default AND applying it cannot evict: the cap lives
            # in the tier's shared header, so a value another job (or its
            # autotune loop) already moved must not be clobbered - and a
            # concurrent job's resident entries under the untouched default
            # must not be evicted down to fit THIS reader's smaller dataset
            cache.set_target_bytes(
                planner_verdict.knobs["cache_mem"].value * 2 ** 20)
        if reader.autotune is not None and cache.l1_enabled:
            # the memory-vs-worker-count trade becomes a live knob: the L1
            # residency cap (MB) rides the same starved/blocked signals as
            # the structural knobs (docs/operations.md "Warm cache")
            mb = 2 ** 20
            reader.autotune.attach_cache_memory(
                get=lambda: max(1, cache.get_target_bytes() // mb),
                set_=lambda n: cache.set_target_bytes(n * mb) // mb,
                lo_mb=16, hi_mb=max(16, int(0.8 * cache.l1_size_bytes) // mb))
    if decode_split_cell is not None and reader.autotune is not None:
        # the split becomes a live autotune knob: starved consumers push
        # decode work off the host (toward device), consumer-bound pipelines
        # pull it back; decisions ride autotune.* counters and the
        # autotune.decode_split gauge (flight-recorder knob trajectory)
        reader.autotune.attach_decode_split(
            get=lambda: int(decode_split_cell.value),
            set_=reader.set_decode_split)
    return reader


def _is_sequence_like(field) -> bool:
    """A variable-length 1-D column (token documents and other list data):
    image-only knobs (decode_roi, decode_placement) must refuse these with
    guidance instead of an image-centric shape error.  ONE definition -
    the sequence package's - so the two layers never classify a field
    differently (lazy import; sequence.dataset does not import reader at
    module level)."""
    from petastorm_tpu.sequence.dataset import is_sequence_field

    return is_sequence_field(field)


_ROI_MODES = ("center", "random")


def _normalize_roi_spec(name: str, spec) -> tuple:
    """Validate/normalize one decode_roi entry; returns the spec tuple."""
    spec = tuple(spec)
    if len(spec) == 3 and spec[0] in _ROI_MODES:
        mode, h, w = spec
        if not (isinstance(h, int) and isinstance(w, int) and h > 0 and w > 0):
            raise PetastormTpuError(
                f"decode_roi[{name!r}]: ({mode!r}, h, w) needs positive int"
                f" crop dims; got {spec}")
        return spec
    if len(spec) == 4 and all(isinstance(v, int) for v in spec):
        y, x, h, w = spec
        if y < 0 or x < 0 or h < 1 or w < 1:
            raise PetastormTpuError(
                f"decode_roi[{name!r}]: (y, x, h, w) needs y, x >= 0 and"
                f" h, w >= 1; got {spec}")
        return spec
    raise PetastormTpuError(
        f"decode_roi[{name!r}] must be (y, x, h, w), ('center', h, w) or"
        f" ('random', h, w); got {spec!r}")


def _validate_decode_roi(decode_roi, schema, read_fields, decode_placement,
                         ngram) -> None:
    from petastorm_tpu.codecs import CompressedImageCodec

    if ngram is not None:
        raise PetastormTpuError("decode_roi is not supported with ngram"
                                " readers")
    for name, spec in decode_roi.items():
        spec = _normalize_roi_spec(name, spec)
        if name not in schema:
            raise PetastormTpuError(f"decode_roi field {name!r} not in schema"
                                    f" {[f.name for f in schema]}")
        if name not in read_fields:
            raise PetastormTpuError(
                f"decode_roi field {name!r} is not being read (excluded by"
                " schema_fields)")
        if decode_placement and decode_placement.get(name, "host") != "host":
            raise PetastormTpuError(
                f"decode_roi field {name!r} cannot also use decode_placement="
                f"{decode_placement[name]!r}: coefficient planes carry the"
                " full image (crop on-device instead, ops/augment.py)")
        field = schema[name]
        if _is_sequence_like(field):
            raise PetastormTpuError(
                f"decode_roi field {name!r} is a variable-length sequence"
                f" field (shape {field.shape}, codec {field.codec!r}):"
                " decode_roi is a partial IMAGE decode and does not apply to"
                " token columns. Filter documents with a predicate (pushed"
                " down before decode) or slice tokens in the packer"
                " (petastorm_tpu.sequence).")
        if not (field.is_fixed_shape and field.dtype == np.dtype("uint8")
                and isinstance(field.codec, CompressedImageCodec)
                and len(field.shape) in (2, 3)):
            raise PetastormTpuError(
                f"decode_roi field {name!r} must be a fixed-shape uint8"
                f" CompressedImageCodec image; got {field.codec!r} shape"
                f" {field.shape} dtype {field.dtype}")
        full_h, full_w = field.shape[:2]
        crop_h, crop_w = (spec[1], spec[2]) if spec[0] in _ROI_MODES \
            else (spec[2], spec[3])
        y0 = 0 if spec[0] in _ROI_MODES else spec[0]
        x0 = 0 if spec[0] in _ROI_MODES else spec[1]
        if y0 + crop_h > full_h or x0 + crop_w > full_w:
            raise PetastormTpuError(
                f"decode_roi[{name!r}] crop {spec} exceeds the stored image"
                f" geometry ({full_h}, {full_w})")


def _apply_roi_schema(schema: Schema, decode_roi) -> Schema:
    """Crop-shaped view of ``schema``: decode_roi fields' leading (H, W)
    become the crop dims (what the delivered columns actually are)."""
    import dataclasses as _dc

    fields = []
    for f in schema:
        spec = decode_roi.get(f.name)
        if spec is not None:
            crop_h, crop_w = (spec[1], spec[2]) if spec[0] in _ROI_MODES \
                else (spec[2], spec[3])
            f = _dc.replace(f, shape=(crop_h, crop_w) + tuple(f.shape[2:]))
        fields.append(f)
    return Schema(schema.name, fields)


def _validate_decode_placement(decode_placement, schema, read_fields,
                               transform_spec, ngram, predicate=None) -> tuple:
    """Check a decode_placement mapping; returns (device fields, mixed
    subset, live-split subset).

    Device placement = the pool worker runs only libjpeg's entropy decode and
    ships coefficient planes; the jax loader runs the FLOP-heavy rest
    (dequant + IDCT + upsample + color) on the TPU (ops/jpeg.py).

    ``'device'`` is the uniform-geometry fast path: fixed-shape plane columns
    (batch/shuffle/shm as ordinary arrays), one XLA compile for the whole
    dataset.  ``'device-mixed'`` supports datasets mixing jpeg geometries/
    subsamplings: rows travel as object cells and the loader finishes them
    on-chip in fixed canvases (compiles bounded by chroma layouts x
    canvases x group sizes; see JaxDataLoader for the canvas contract).
    """
    if not decode_placement:
        return [], frozenset(), frozenset()
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.native import image as native_image

    device_fields = []
    mixed_fields = set()
    split_fields = set()
    for name, place in decode_placement.items():
        if place not in ("host", "device", "device-mixed", "auto"):
            raise PetastormTpuError(
                f"decode_placement[{name!r}] must be 'host', 'device',"
                f" 'device-mixed' or 'auto', got {place!r}")
        if name not in schema:
            raise PetastormTpuError(f"decode_placement field {name!r} not in"
                                    f" schema {[f.name for f in schema]}")
        if place == "host":
            continue
        if _is_sequence_like(schema[name]):
            raise PetastormTpuError(
                f"decode_placement field {name!r} is a variable-length"
                f" sequence field (shape {schema[name].shape}, codec"
                f" {schema[name].codec!r}): device decode placement is for"
                " jpeg image columns (the worker ships coefficient planes)."
                " Token columns decode host-side; deliver them through"
                " petastorm_tpu.sequence (packing + JaxDataLoader).")
        if not native_image.available():
            raise PetastormTpuError(
                f"decode_placement={place!r} needs the native image library"
                " (petastorm_tpu/native/image_decode.cpp failed to build on"
                " this host); use host decode")
        field = schema[name]
        codec = field.codec
        if not (isinstance(codec, CompressedImageCodec)
                and codec.image_codec == "jpeg"):
            raise PetastormTpuError(
                f"decode_placement={place!r} requires a jpeg"
                f" CompressedImageCodec field; {name!r} has"
                f" {type(codec).__name__}"
                + (f"({codec.image_codec})" if isinstance(
                    codec, CompressedImageCodec) else "")
                + ". PNG's deflate stream cannot be entropy-split for on-chip"
                " decode - store images as jpeg for device decode.")
        if place in ("device", "auto") and not field.is_fixed_shape:
            raise PetastormTpuError(
                f"decode_placement='device' field {name!r} needs a fixed shape"
                f" (got {field.shape}): XLA compiles per geometry. For"
                " mixed-geometry datasets use decode_placement='device-mixed'")
        if (len(field.shape) not in (2, 3)
                or (len(field.shape) == 3 and field.shape[2] not in (1, 3))):
            raise PetastormTpuError(
                f"decode_placement={place!r} field {name!r} must be (H, W),"
                f" (H, W, 1) or (H, W, 3); got {field.shape}")
        if ngram is not None:
            raise PetastormTpuError(
                f"decode_placement={place!r} is not supported with ngram readers")
        if transform_spec is not None:
            raise PetastormTpuError(
                f"decode_placement={place!r} cannot be combined with a"
                " transform_spec: the transform would see raw jpeg bytes, not"
                " pixels. Decode on host, or transform on device after the"
                " loader.")
        if predicate is not None and name in predicate.get_fields():
            raise PetastormTpuError(
                f"predicate field {name!r} uses decode_placement={place!r}:"
                " the predicate would see coefficient planes, not pixels."
                " Decode it on host, or predicate on other fields.")
        if name not in read_fields:
            raise PetastormTpuError(
                f"decode_placement={place!r} field {name!r} is not being read"
                " (excluded by schema_fields); drop it from decode_placement"
                " or add it to schema_fields")
        device_fields.append(name)
        if place == "device-mixed":
            mixed_fields.add(name)
        elif place == "auto":
            split_fields.add(name)
    return device_fields, frozenset(mixed_fields), frozenset(split_fields)


class _SkippedItem:
    """Reorder-stage marker for a policy-skipped ordinal: accounted and
    digested when the stage reaches its plan position, so two runs that
    quarantine the same item produce the same certificate."""

    __slots__ = ()


_SKIPPED = _SkippedItem()


class Reader:
    """Iterator over decoded data; context manager owning the executor.

    Row path: one namedtuple per row.  Batch path: one namedtuple of column arrays
    per rowgroup (reference reader.py:277-290).
    """

    def __init__(self, info, schema: Schema, plan: ReadPlan, executor, worker,
                 num_epochs: Optional[int], batched_output: bool,
                 start_item: int = 0, ngram=None, telemetry=None,
                 error_policy: Optional[ErrorPolicy] = None,
                 stall_warn_s: Optional[float] = None,
                 stall_abort_s: Optional[float] = None,
                 metrics_port: Optional[int] = None,
                 flight_record_path: Optional[str] = None,
                 sample_interval_s: Optional[float] = None,
                 autotune_policy=None,
                 deterministic: str = "off",
                 shuffle_seed: Optional[int] = None,
                 digest_state: Optional[dict] = None):
        #: petastorm_tpu.telemetry recorder shared by the whole pipeline
        #: (no-op unless enabled); ``reader.telemetry.pipeline_report()``
        #: renders the stage-utilization bottleneck summary
        self.telemetry = _resolve_telemetry(telemetry)
        self._m_results_empty = self.telemetry.counter(
            "queue.results_empty_wait_s")
        self._m_rows_emitted = self.telemetry.counter("reader.rows_emitted")
        self._m_batches = self.telemetry.counter("reader.batches_consumed")
        self._m_skipped = self.telemetry.counter("errors.skipped_rowgroups")
        #: resolved ``on_error`` policy (None = raise mode)
        self._error_policy = error_policy
        #: quarantine ledger: one entry per skipped work item
        self._quarantine: list = []
        self.dataset_info = info
        self.schema = schema
        self.batched_output = batched_output
        self.ngram = ngram
        #: schema of the columnar batches iter_batches yields (differs from
        #: ``schema`` for ngram readers: '<offset>/<field>' / stacked entries)
        self.output_schema = schema
        if ngram is not None:
            self._ngram_views = ngram.resolve_schema(schema)
            self._ngram_types = ngram.make_namedtuple_types(schema)
            self.output_schema = ngram.output_schema(schema)
        self._plan = plan
        self._executor = executor
        self._num_epochs = num_epochs
        self._stopped = False
        self._stall_aborted = False
        # latched per reader: an explicit kwarg wins; None falls back to the
        # env var, which wins over the module defaults (which tests may
        # monkeypatch); <= 0 disables the respective behavior
        self._stall_warn_s = (float(stall_warn_s) if stall_warn_s is not None
                              else _env_seconds("PETASTORM_TPU_STALL_WARN_S",
                                                _STALL_WARN_S))
        self._stall_abort_s = (float(stall_abort_s)
                               if stall_abort_s is not None
                               else _env_seconds("PETASTORM_TPU_STALL_ABORT_S",
                                                 _STALL_ABORT_S))
        #: shared storage circuit breaker (petastorm_tpu.retry), set by
        #: make_reader when io_retries arms one; None otherwise
        self.circuit_breaker = None
        from petastorm_tpu.pool import SerialExecutor
        if isinstance(executor, SerialExecutor) and self._stall_abort_s > 0:
            # the serial pool runs work inline inside get(), so the
            # reader-side stall loop (which only observes BETWEEN get calls)
            # can never fire for a wedged transform there; the serial pool's
            # own watchdog covers stall_warn_s, but abort has no observer
            # (docs/operations.md "Liveness & stragglers")
            logger.warning(
                "stall_abort_s=%.0f is inoperative with"
                " reader_pool_type='serial': work runs inline inside the"
                " consumer's get(), so a wedged work item blocks the stall"
                " loop itself (the serial watchdog still WARNS via"
                " stall_warn_s). Use the thread or process pool when stall"
                " abort matters.", self._stall_abort_s)
        self.last_row_consumed = False
        #: set by make_reader after construction (decode_placement='device')
        self.device_decode_fields: list = []
        #: subset using the mixed-geometry wire format ('device-mixed')
        self.device_decode_mixed: frozenset = frozenset()
        #: subset under the LIVE host<->device decode split ('auto')
        self.device_decode_split: frozenset = frozenset()
        #: shared split cell (set by make_reader when 'auto' fields exist)
        self._decode_split_cell = None
        #: the host-wide shared warm-cache tier (petastorm_tpu.cache_shared;
        #: set by make_reader for cache_type='shared').  The reader is the
        #: tier's telemetry publisher: shared-header counter deltas fold into
        #: this registry as the cache.* series on the consume path
        self.warm_cache = None
        self._cache_publish_at = 0.0
        #: static planner verdict (petastorm_tpu.planner.PlanVerdict), set
        #: by make_reader when the planner ran; stop() persists this run's
        #: converged knobs as the dataset's flight profile
        self.planner = None
        self._profile_written = False

        self._start_item = start_item
        self._consumed_items = 0
        # exact contiguous consumed prefix: pools complete items out of
        # ventilation order, so counting alone cannot give a resume cursor
        # that never loses items - ordinals on each batch can
        self._prefix = start_item
        self._consumed_ordinals: set = set()
        self._ordinals_seen = False

        # -- seed-stable delivery (docs/operations.md "Reproducibility") ---
        from petastorm_tpu.seeding import StreamDigest

        #: 'seed' = the reorder stage below releases batches in PLAN order
        #: (worker timing, hedge wins, requeues, resizes and the service hop
        #: all collapse to one stream); 'off' = completion-order delivery
        self.deterministic = deterministic
        #: the plan seed, re-exposed so downstream stages (JaxDataLoader's
        #: shuffle buffers) derive their RNGs from the same root via
        #: seeding.seed_stream
        self.shuffle_seed = shuffle_seed
        # reorder stage state: completed batches (and skip markers) held
        # until every lower plan ordinal has been released.  The stage keeps
        # draining the results queue while it waits (the pool never stalls
        # behind it); its memory is bounded by the VENTILATOR's release
        # window below - queue bounds alone would let one straggling
        # rowgroup hand the stage a whole epoch of completed batches
        self._det_held: dict = {}
        self._det_next = start_item
        self._det_warned_unordered = False
        self._det_release_window = None
        if deterministic == "seed":
            capacity = getattr(executor, "inflight_capacity", None)
            capacity = capacity() if callable(capacity) else None
            if capacity is not None and capacity < (1 << 20):
                # 2x the executor's own window: a full extra pipeline of
                # slack (the pacing never costs throughput) while keeping
                # held memory bounded; effectively-unbounded results queues
                # (2**30 bound) keep the old unbounded behavior - the user
                # asked for it
                self._det_release_window = max(16, 2 * capacity)
        #: running stream certificate (petastorm_tpu.seeding.StreamDigest):
        #: maintained on EVERY reader (cheap crc chain); stable across
        #: configurations only under deterministic='seed'
        self._digest = StreamDigest(state=digest_state)
        self._g_digest = self.telemetry.gauge("stream.digest")
        self._m_reordered = self.telemetry.counter("reader.reordered_batches")
        # ordinal -> (epoch, WorkItem) lookup cache: the digest needs each
        # batch's plan-independent work-item identity; epoch item lists are
        # recomputed once per epoch (two cached epochs cover out-of-order
        # deliveries straddling an epoch boundary in 'off' mode)
        self._epoch_items_cache: dict = {}
        self._current: Optional[ColumnBatch] = None
        self._current_pos = 0
        self._row_buffer: list = []
        self._row_pos = 0
        self._namedtuple_type = schema.make_namedtuple_type()
        self._field_names = list(schema.fields)

        # -- live observability (docs/operations.md "Live monitoring") -----
        #: continuous time-series sampler over ``telemetry`` (None when
        #: telemetry is disabled); ``reader.sampler.series()`` is the live
        #: rate/latency history, and the flight recorder's data source
        self.sampler = None
        #: localhost-only Prometheus endpoint (None unless ``metrics_port``);
        #: the bound port is ``reader.metrics_server.port``
        self.metrics_server = None
        #: closed-loop autotune controller (petastorm_tpu.autotune; None
        #: unless ``make_reader(autotune=...)`` / ``workers_count='auto'``
        #: armed it); JaxDataLoader attaches its prefetch knob to it
        self.autotune = None
        self._flight_record_path = flight_record_path
        self._flight_record: Optional[dict] = None
        self._final_snapshot: Optional[dict] = None
        self._observability_closed = False
        try:
            if self.telemetry.enabled:
                from petastorm_tpu.telemetry.sampler import (
                    DEFAULT_INTERVAL_S, MetricsSampler)

                interval = (float(sample_interval_s)
                            if sample_interval_s is not None
                            else _env_seconds(
                                "PETASTORM_TPU_SAMPLE_INTERVAL_S",
                                DEFAULT_INTERVAL_S))
                if interval > 0:  # <= 0 keeps telemetry, disables sampling
                    self.sampler = MetricsSampler(self.telemetry,
                                                  interval_s=interval)
                    self.sampler.start()
            if flight_record_path and self.sampler is None:
                # the artifact was explicitly requested but nothing will feed
                # it - say so NOW, not after the incident the record was for
                logger.warning(
                    "flight_record_path=%r is inert: sampling is disabled"
                    " (sample_interval_s <= 0 or telemetry has no sampler);"
                    " no flight record will be written on failure",
                    flight_record_path)
            if metrics_port is not None:
                from petastorm_tpu.telemetry.export import MetricsExportServer

                self.metrics_server = MetricsExportServer(
                    self.telemetry, sampler=self.sampler, port=metrics_port)
                self.metrics_server.start()

            self._executor.start(worker)
            self._ventilator = Ventilator(
                executor, plan, num_epochs, start_item=start_item,
                telemetry=self.telemetry,
                release_window=self._det_release_window,
                release_progress=self._det_release_progress)
            self._expected_items = self._ventilator.total_items
            self._ventilator.start()
            if autotune_policy is not None:
                if self.sampler is None:
                    # the controller decides from sampled series; without a
                    # sampler it would be flying blind - refuse loudly
                    logger.warning(
                        "autotune is inert: sampling is disabled"
                        " (sample_interval_s <= 0); the pipeline runs with"
                        " its static knobs")
                else:
                    from petastorm_tpu.autotune import AutotuneController

                    self.autotune = AutotuneController(
                        self._executor, self.sampler, self.telemetry,
                        policy=autotune_policy)
                    self.autotune.start()
        except BaseException:
            # the reader never came to life (incl. a metrics-port bind
            # failure): release the observability layer - the sampler
            # thread, and any bound metrics port - or a construct-retry
            # loop leaks a 1 Hz sampler per attempt and hits EADDRINUSE.
            # The executor may already have live workers (a Ventilator
            # failure lands here after start): stop them too, or each retry
            # leaks a polling worker plane
            self._close_observability()
            try:
                self._executor.stop()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                logger.debug("executor stop during construction failure"
                             " cleanup failed", exc_info=True)
            raise

    # -- iteration ------------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._stopped:
            raise ReaderClosedError("Reader is stopped")
        if self.device_decode_fields:
            # the worker shipped raw jpeg bytes for these fields; only the
            # jax loader (ops/jpeg.py) finishes the decode on-chip.  Yielding
            # here would hand out object-dtype bytes where the schema
            # promises (H, W, C) uint8 pixels.
            raise PetastormTpuError(
                f"fields {self.device_decode_fields} use"
                " decode_placement='device': their batches carry raw jpeg"
                " bytes, not pixels. Consume this reader through"
                " petastorm_tpu.jax.JaxDataLoader (which decodes on-chip),"
                " or use decode_placement='host' for row/tf/pytorch access.")
        if self.batched_output:
            batch = self._next_batch()
            return self._namedtuple_type(**{n: batch.columns[n]
                                            for n in self.schema.fields})
        if self.ngram is None:
            # hot row loop: materialize a whole rowgroup's namedtuples in one
            # C-level map(zip(...)) pass, then hand them out by index - far
            # less per-row python than building each row on demand
            if self._row_pos >= len(self._row_buffer):
                cols = self._next_batch().columns
                self._row_buffer = list(map(
                    self._namedtuple_type._make,
                    zip(*[cols[n] for n in self._field_names])))
                self._row_pos = 0
            row = self._row_buffer[self._row_pos]
            self._row_pos += 1
            if (self._row_pos >= len(self._row_buffer)
                    and self._all_items_consumed()):
                self.last_row_consumed = True
            return row
        if self._current is None or self._current_pos >= self._current.num_rows:
            self._current = self._next_batch()
            self._current_pos = 0
        pos = self._current_pos
        self._current_pos += 1
        if (self._current_pos >= self._current.num_rows
                and self._all_items_consumed()):
            self.last_row_consumed = True
        if self.ngram.stack_timesteps:
            raise PetastormTpuError(
                "stack_timesteps NGram readers are columnar-only: use"
                " iter_batches() or the jax loader")
        # one window: {offset: namedtuple} (reference row-path shape)
        return self.ngram.row(self._ngram_views, self._ngram_types,
                              self._current, pos)

    def iter_batches(self):
        """Yield raw ColumnBatches (the TPU feed path: no namedtuple wrapping).

        Used by petastorm_tpu.jax loaders; do not mix with ``__next__`` on the
        same reader instance.  Ends cleanly (StopIteration) if the reader is
        stopped mid-iteration.
        """
        while True:
            try:
                yield self._next_batch()
            except (StopIteration, ReaderClosedError):
                return

    def _all_items_consumed(self) -> bool:
        return (self._expected_items is not None
                and self._consumed_items >= self._expected_items)

    def _stalled_stage(self) -> str:
        """Best-effort name of the stage the pipeline is stalled in (the
        telemetry dominant stage - where cumulative busy time concentrated);
        '' when telemetry is disabled or has no samples."""
        if not self.telemetry.enabled:
            return ""
        try:
            return dominant_stage(self.telemetry.snapshot())
        except Exception:  # noqa: BLE001 - diagnostics must not mask a stall
            return ""

    def _next_batch(self) -> ColumnBatch:
        """Next non-empty ColumnBatch, or StopIteration at end of all epochs.

        Stall detection: when no result arrives for ``stall_warn_s`` seconds
        (default 120; ``PETASTORM_TPU_STALL_WARN_S`` fallback) a WARNING
        names the stuck workers and their work items (executor heartbeats)
        plus the telemetry dominant stage when enabled; ``stall_abort_s``
        (default off; ``PETASTORM_TPU_STALL_ABORT_S`` fallback) escalates a
        longer stall to a PipelineStallError carrying the diagnostics
        snapshot, so a wedged pipeline fails loudly instead of waiting
        forever.
        """
        last_progress = time.monotonic()
        warned_at = 0.0
        tele = self.telemetry
        while True:
            if self._stopped:
                raise ReaderClosedError("Reader was stopped mid-iteration")
            if self._all_items_consumed():
                self.last_row_consumed = True
                raise StopIteration
            if self.deterministic == "seed" and self._det_held:
                # reorder stage: release the next PLAN ordinal if its result
                # (or skip marker) already arrived; otherwise keep draining
                # the executor below - holding completed-out-of-order batches
                # here (bounded: the Ventilator's release window stops new
                # work more than one window past the release point) is what
                # makes worker timing, hedge wins, requeues, resizes and the
                # service hop all collapse to the same delivered stream.
                # Once degraded (an ordinal-less batch arrived), drain
                # whatever is held in plan order regardless of gaps - a
                # missing ordinal must not wedge batches already decoded.
                key = None
                if self._det_next in self._det_held:
                    key = self._det_next
                elif self._det_warned_unordered:
                    key = min(self._det_held)
                if key is not None:
                    ready = self._det_held.pop(key)
                    self._det_next = max(self._det_next, key + 1)
                    if ready is _SKIPPED:
                        self._digest_skip(key)
                        self._account_consumed(key)
                        continue
                    released = self._deliver_released(ready)
                    if released is not None:
                        return released
                    continue  # empty batch (predicate filtered everything)
            # time blocked inside executor.get = the consumer starving on an
            # empty results queue (the "worker plane is the bottleneck" signal)
            t0 = time.perf_counter() if tele.enabled else None
            try:
                # 'results-empty' spans each get: microseconds long when a
                # result was ready, the starvation itself when none was
                with (tele.span("results-empty") if t0 is not None
                      else _NULL_CONTEXT):
                    batch = self._executor.get(timeout=_GET_TIMEOUT_S)
            except WorkerError as exc:
                if t0 is not None:
                    self._m_results_empty.add(time.perf_counter() - t0)
                # on_error skip policies quarantine attributable failures
                # and keep iterating; anything else propagates
                self._skip_or_raise(exc)
                last_progress = time.monotonic()
                continue
            except queue.Empty:
                if t0 is not None:
                    self._m_results_empty.add(time.perf_counter() - t0)
                stalled = time.monotonic() - last_progress
                if self._stall_abort_s > 0 and stalled > self._stall_abort_s:
                    self._stall_aborted = True
                    # flight record BEFORE the diagnostics snapshot (so the
                    # raised error carries it) and before stop() ends sampling
                    self._record_flight(
                        f"PipelineStallError: no result for {stalled:.0f}s")
                    diag = self.diagnostics  # snapshot before stop() mutates it
                    stage = self._stalled_stage()
                    # stop the pipeline like the worker-failure path does:
                    # a caller that catches this must not inherit a live
                    # ventilator + polling workers
                    self.stop()
                    # the message interpolates a TRIMMED pipeline state: the
                    # flight record (whole sampled series) rides .diagnostics
                    # for programmatic triage, not the traceback text
                    msg_diag = {k: v for k, v in diag.items()
                                if k not in ("flight_recorder", "telemetry")}
                    raise PipelineStallError(
                        f"No result for {stalled:.0f}s (stall_abort_s="
                        f"{self._stall_abort_s:.0f})"
                        + (f"; busiest stage: {stage}" if stage else "")
                        + f"; pipeline state: {msg_diag}", diagnostics=diag)
                if (self._stall_warn_s > 0 and stalled > self._stall_warn_s
                        and stalled - warned_at > self._stall_warn_s):
                    warned_at = stalled
                    stage = self._stalled_stage()
                    logger.warning(
                        "Reader has produced no batch for %.0fs%s; pipeline"
                        " state: %s", stalled,
                        f" (busiest stage: {stage})" if stage else "",
                        self.diagnostics)
                continue
            if t0 is not None:
                self._m_results_empty.add(time.perf_counter() - t0)
                self._m_batches.add(1)
                self._m_rows_emitted.add(batch.num_rows)
            last_progress = time.monotonic()
            if self.warm_cache is not None:
                self._maybe_publish_cache(last_progress)
            if self.deterministic == "seed" and batch.ordinal is not None \
                    and not self._det_warned_unordered:
                # stash for in-order release at the loop top; release
                # happens next iteration (possibly immediately, when this
                # IS the next expected ordinal).  After a degrade the stash
                # is bypassed - a missing ordinal would hold these forever
                if batch.ordinal != self._det_next:
                    self._m_reordered.add(1)
                self._det_held[batch.ordinal] = batch
                self._check_reorder_window()
                continue
            if self.deterministic == "seed" and batch.ordinal is None \
                    and not self._det_warned_unordered:
                # a transport dropped the ventilation ordinals: in-order
                # release is impossible, degrade loudly to arrival order
                # (the loop top flushes anything already held, in plan order)
                self._det_warned_unordered = True
                logger.warning(
                    "deterministic='seed' degraded: a batch arrived without"
                    " a ventilation ordinal (transport dropped it); stream"
                    " order now follows completion order and the digest is"
                    " not comparable across configurations")
            released = self._deliver_released(batch)
            if released is not None:
                return released
            # empty batch (predicate filtered everything): keep pulling

    def _account_consumed(self, ordinal) -> None:
        """Count one work item as consumed and advance the exact contiguous
        consumed prefix - the resume-cursor invariant (state_dict position
        exactness under out-of-order pools).  The single implementation
        serves both delivered batches and policy-skipped items."""
        self._consumed_items += 1
        if ordinal is not None:
            self._ordinals_seen = True
            self._consumed_ordinals.add(ordinal)
            while self._prefix in self._consumed_ordinals:
                self._consumed_ordinals.discard(self._prefix)
                self._prefix += 1

    # -- seed-stable delivery (docs/operations.md "Reproducibility") ----------

    def _locate_ordinal(self, ordinal: int):
        """(epoch, index-within-epoch) of an absolute plan ordinal."""
        plan = self._plan
        if isinstance(plan, ElasticResumePlan):
            leftover = plan.leftover_len
            if ordinal < leftover:
                return 0, ordinal
            ipe = plan.base_items_per_epoch
            if ipe <= 0:
                return 0, ordinal
            return 1 + (ordinal - leftover) // ipe, (ordinal - leftover) % ipe
        ipe = self._ventilator.items_per_epoch
        if ipe <= 0:
            return 0, ordinal
        return ordinal // ipe, ordinal % ipe

    def _work_item_for(self, ordinal):
        """(epoch, WorkItem or None) behind a delivered ordinal - the digest
        needs the item's plan-independent identity (rowgroup global index +
        slice), which the wire does not carry; the deterministic plan
        recomputes it.  Two epochs of items stay cached (out-of-order
        deliveries straddle epoch boundaries in 'off' mode)."""
        if ordinal is None:
            return 0, None
        epoch, idx = self._locate_ordinal(int(ordinal))
        items = self._epoch_items_cache.get(epoch)
        if items is None:
            while len(self._epoch_items_cache) >= 2:
                self._epoch_items_cache.pop(min(self._epoch_items_cache))
            items = self._plan.epoch_items(epoch)
            self._epoch_items_cache[epoch] = items
        if 0 <= idx < len(items):
            return epoch, items[idx]
        return epoch, None

    def _digest_deliver(self, batch: ColumnBatch) -> None:
        """Fold one released batch into the stream certificate."""
        epoch, item = self._work_item_for(batch.ordinal)
        if item is not None:
            start, stop = item.row_slice()
            self._digest.record_batch(epoch, batch.ordinal,
                                      item.row_group.global_index,
                                      item.row_group.row_group,
                                      start, stop, batch.num_rows)
        else:
            self._digest.record_batch(epoch, batch.ordinal, -1, -1, 0, 0,
                                      batch.num_rows)
        if self.telemetry.enabled:
            self._g_digest.set(self._digest.combined)

    def _digest_skip(self, ordinal) -> None:
        """Fold one policy-skipped work item into the stream certificate."""
        epoch, item = self._work_item_for(ordinal)
        self._digest.record_skip(
            epoch, ordinal,
            item.row_group.global_index if item is not None else -1,
            item.row_group.row_group if item is not None else -1)
        if self.telemetry.enabled:
            self._g_digest.set(self._digest.combined)

    def _deliver_released(self, batch: ColumnBatch):
        """Delivery bookkeeping shared by BOTH release paths (the reorder
        stage's in-plan-order release and direct completion-order delivery):
        digest fold, epoch accounting, end-of-stream flagging.  Returns the
        batch when it carries rows, None for an empty one (predicate
        filtered everything - the caller keeps pulling)."""
        self._digest_deliver(batch)
        self._account_consumed(batch.ordinal)
        if batch.num_rows > 0:
            if self.batched_output and self._all_items_consumed():
                # batch path: flag as the final value is returned; the row
                # path flags only after the last row is actually popped
                self.last_row_consumed = True
            return batch
        return None

    def _det_release_progress(self) -> int:
        """The reorder stage's release point, read by the Ventilator's
        release window (consumer-thread writes, ventilator-thread reads: a
        plain int under the GIL).  In-order release makes the contiguous
        consumed prefix exactly the released count; after a degrade the
        window must not gate ventilation on a prefix that ordinal-less
        batches can no longer advance."""
        if self._det_warned_unordered:
            return 1 << 62
        return self._prefix

    def _check_reorder_window(self) -> None:
        """One-time warning when the reorder stage holds more batches than
        the executor can have in flight AND the expected ordinal is in
        nobody's ledger (a lost-ordinal transport bug - no result will ever
        release the stream); the stall watchdog, not silent unbounded
        buffering, is what ends the wait.  A requeued straggler legitimately
        falls far behind fresh ventilation, so window overflow alone is not
        the signal - the ledger check is."""
        if self._det_warned_unordered:
            return
        capacity = getattr(self._executor, "inflight_capacity", None)
        capacity = capacity() if callable(capacity) else None
        if capacity is None or len(self._det_held) <= capacity:
            return
        if self._det_next in self._det_held:
            return  # just arrived (settled + stashed); releases next loop
        is_inflight = getattr(self._executor, "is_inflight", None)
        if callable(is_inflight) and is_inflight(self._det_next):
            return  # straggling/requeued, not lost: its result will come
        self._det_warned_unordered = True
        logger.warning(
            "deterministic reorder stage holds %d completed batches (past"
            " the executor's in-flight window of %d) while plan ordinal %d"
            " is in nobody's ledger - the expected item looks lost; the"
            " stall watchdog will abort if it never arrives. Pipeline"
            " state: %s", len(self._det_held), capacity, self._det_next,
            self.diagnostics)

    @property
    def stream_digest(self) -> dict:
        """The stream certificate so far (petastorm_tpu.seeding.StreamDigest
        summary): per-epoch and combined crc chains over released work items
        + batch boundaries.  Under ``deterministic='seed'`` two runs with
        the same (seed, epochs) match bit-for-bit regardless of worker
        count, executor flavor, chaos or transport; diff it in O(1) instead
        of diffing delivered tensors."""
        return self._digest.summary()

    def _maybe_publish_cache(self, now: float) -> None:
        """Fold the shared warm tier's cross-process counters into this
        reader's telemetry as the ``cache.*`` series (time-gated: the shared
        index lock must not be taken per batch).  One publisher per reader -
        workers only bump the shared header, so nothing double-counts."""
        if now - self._cache_publish_at < 0.5:
            return
        self._cache_publish_at = now
        try:
            self.warm_cache.publish_telemetry()
        except Exception:  # noqa: BLE001 - observability must not break reads
            logger.debug("warm-cache telemetry publish failed", exc_info=True)

    # -- flight recorder (docs/operations.md "Live monitoring") ---------------

    def _record_flight(self, reason: str) -> None:
        """Capture the flight record - the last ~60 s of sampled series plus
        the trace tail - once, at the FIRST terminal failure, and dump it to
        ``flight_record_path`` when set.  Best-effort: the crash artifact
        must never mask the crash itself."""
        if self._flight_record is not None or self.sampler is None:
            return
        try:
            from petastorm_tpu.telemetry.sampler import (dump_flight_record,
                                                         flight_record)

            # service readers enrich the artifact with the dispatcher's
            # structured fleet-event tail (promotions, requeues, autoscale
            # decisions) so one JSONL captures the fleet's last ~60s, not
            # just this client's curves; best-effort side connection
            fetch = getattr(self._executor, "fetch_fleet_events", None)
            fleet_events = fetch() if callable(fetch) else None
            self._flight_record = flight_record(self.sampler, reason=reason,
                                                fleet_events=fleet_events)
            # the certificate up to the failure: two runs' incident records
            # can be diffed for where their streams diverged
            self._flight_record["stream_digest"] = self._digest.summary()
            if self._flight_record_path:
                dump_flight_record(self._flight_record,
                                   self._flight_record_path)
                logger.warning(
                    "Flight record (%d sampled points) written to %s",
                    len(self._flight_record["points"]),
                    self._flight_record_path)
        except Exception:  # noqa: BLE001 - diagnostics must not mask failure
            logger.warning("flight-record capture failed", exc_info=True)

    # -- failure handling (docs/operations.md "Failure handling") -------------

    def _skip_or_raise(self, exc: WorkerError) -> None:
        """Quarantine an attributable worker failure under a skip policy.

        Unattributable failures (all workers died, stall abort - no work
        item to blame) and failures under the default ``on_error='raise'``
        propagate unchanged.  A skipped item still counts toward epoch
        accounting: the epoch ends at the same counted event, just with the
        quarantined rowgroup's rows missing - exactly once, never duplicated.
        """
        policy = self._error_policy
        if policy is None or exc.item is None:
            # terminal in both modes (raise-mode failure, or an
            # unattributable failure under a skip policy): capture the
            # flight record while the sampler still runs
            self._record_flight(
                f"WorkerError ({exc.exc_type or 'unattributable'},"
                f" kind={exc.kind})")
            if policy is not None:
                # terminal under a skip policy (all workers died, or another
                # unattributable failure): the pool was constructed with
                # stop_on_failure=False, so stop the pipeline here - a
                # caller that catches this must not inherit a live
                # ventilator + polling workers (same contract as the
                # stall-abort path)
                self.stop()
            raise exc
        work = getattr(exc.item, "item", exc.item)
        rg = getattr(work, "row_group", None)
        message = str(exc)
        entry = {"ordinal": exc.ordinal,
                 "path": getattr(rg, "path", None),
                 "row_group": getattr(rg, "row_group", None),
                 "kind": exc.kind,
                 "exc_type": exc.exc_type,
                 # last traceback line = the remote exception message
                 "error": message.splitlines()[-1] if message else ""}
        self._quarantine.append(entry)
        self._m_skipped.add(1)
        logger.warning(
            "Skipping work item %s (rowgroup %s#%s) after %s error: %s",
            exc.ordinal, entry["path"], entry["row_group"], exc.kind,
            entry["error"])
        if self.deterministic == "seed" and exc.ordinal is not None:
            # account + digest when the reorder stage reaches the skip's
            # plan position (keeps the certificate order-exact); the budget
            # bookkeeping below stays immediate either way.  (After a
            # degrade the loop top drains held entries in plan order, so
            # stashing stays safe there too.)
            self._det_held[exc.ordinal] = _SKIPPED
        else:
            self._digest_skip(exc.ordinal)
            self._account_consumed(exc.ordinal)
        skipped = len(self._quarantine)
        over = None
        if (policy.max_skipped_rowgroups is not None
                and skipped > policy.max_skipped_rowgroups):
            over = (f"{skipped} skipped work items exceed"
                    f" max_skipped_rowgroups={policy.max_skipped_rowgroups}")
        if over is None and policy.max_skipped_fraction is not None:
            # finite readers: fraction of the total expected items.  Infinite
            # readers (num_epochs=None) have no total: use items consumed so
            # far, floored at one epoch - a constant per-epoch corruption
            # rate then yields a constant fraction instead of a cumulative
            # count that would eventually trip any budget
            denom = self._expected_items
            if denom is None:
                denom = max(self._ventilator.items_per_epoch,
                            self._consumed_items)
            if denom and skipped / denom > policy.max_skipped_fraction:
                over = (f"{skipped}/{denom} skipped work items exceed"
                        f" max_skipped_fraction="
                        f"{policy.max_skipped_fraction}")
        if over is not None:
            self._record_flight(f"ErrorBudgetExceededError: {over}")
            diag = self.diagnostics  # snapshot before stop() mutates it
            self.stop()
            raise ErrorBudgetExceededError(
                f"Error budget exceeded: {over}. Quarantined rowgroups: "
                + ", ".join(f"{e['path']}#{e['row_group']}"
                            for e in self._quarantine),
                diagnostics=diag) from exc

    # -- epoch control --------------------------------------------------------

    def reset(self) -> None:
        """Restart iteration; only legal after the epoch finished (reference
        contract, reader.py:423-447)."""
        if self._stopped:
            raise ReaderClosedError("Reader is stopped")
        if not self._all_items_consumed():
            raise EpochNotFinishedError(
                "reset() called mid-epoch: in-flight work items would leak into"
                " the next epoch. Consume the iterator fully first.")
        self._ventilator.stop()
        self._ventilator.join()
        self._start_item = 0
        self._consumed_items = 0
        self._prefix = 0
        self._consumed_ordinals.clear()
        # a reset run is a fresh stream: the reorder stage restarts at
        # ordinal 0 and the certificate chain starts over (comparing a reset
        # run to a fresh reader must compare equal)
        from petastorm_tpu.seeding import StreamDigest

        self._det_held.clear()
        self._det_next = 0
        self._det_warned_unordered = False
        self._epoch_items_cache.clear()
        self._digest = StreamDigest()
        self._row_buffer = []
        self._row_pos = 0
        self._current = None
        self._current_pos = 0
        self.last_row_consumed = False
        self._ventilator = Ventilator(
            self._executor, self._plan, self._num_epochs,
            telemetry=self.telemetry,
            release_window=self._det_release_window,
            release_progress=self._det_release_progress)
        self._expected_items = self._ventilator.total_items
        self._ventilator.start()

    # -- resume support (reference gap: SURVEY.md section 5 checkpoint/resume) --

    def quiesce(self) -> int:
        """Stop issuing new work items; in-flight ones still deliver.

        After calling this, iteration ends once the already-ventilated items
        are consumed, at which point ``state_dict()`` is an EXACT cursor:
        resuming re-reads zero rows.  The drain half lives in
        ``JaxDataLoader.drain()``; plain readers just exhaust the iterator.
        Returns the absolute ordinal the stream will stop at.
        """
        ventilated = self._ventilator.pause_and_join()
        self._expected_items = max(ventilated - self._start_item, 0)
        return ventilated

    def state_dict(self) -> dict:
        """Work-item cursor for ``make_reader(..., resume_from=state)``.

        ``position`` is the exact CONTIGUOUS consumed prefix of the
        deterministic item stream (tracked via per-batch ventilation
        ordinals): resuming from it never loses an item; items completed
        out of order beyond the prefix (at most the in-flight window) are
        re-read.  Same (dataset, seed, shard, epoch-count) settings must be
        passed when resuming.
        """
        position = (self._prefix if self._ordinals_seen
                    else self._start_item + self._consumed_items)
        state = {"position": position,
                 "items_per_epoch": self._ventilator.items_per_epoch,
                 # False means batches arrived without ventilation ordinals
                 # (a transport dropped them) and the cursor degraded to the
                 # count-based position - exact only under in-order pools
                 "ordinal_exact": self._ordinals_seen or self._consumed_items == 0,
                 # stream-certificate chain state: resume_from continues the
                 # chain, so (run A up to quiesce) + (resumed run B) produce
                 # the same combined digest as one uninterrupted run
                 # (docs/operations.md "Reproducibility")
                 "stream_digest": self._digest.state()}
        if isinstance(self._plan, ElasticResumePlan):
            # rebased coordinates: record the translation so this cursor can
            # itself be resumed (plainly or elastically) once past the
            # leftover epoch
            state["elastic_rebased"] = {
                "leftover_len": self._plan.leftover_len,
                "resume_epoch": self._plan.resume_epoch,
                "base_items_per_epoch": self._plan.base_items_per_epoch,
            }
        return state

    # -- lifecycle ------------------------------------------------------------

    def stop(self) -> None:
        """Stop ventilation and the worker pool; in-flight items are discarded.

        Every close path (clean close, stall abort, budget exhaustion, error
        propagation) funnels through here, so this is also where the final
        telemetry snapshot is latched into ``diagnostics['telemetry']`` and
        the sampler / metrics endpoint shut down - a failed run must not lose
        its counters just because nobody held the ``Telemetry`` object.
        """
        self._stopped = True
        if self.planner is not None and not self._profile_written:
            # persist the flight profile BEFORE observability teardown: the
            # payload reads the sampler's trailing points + the autotune
            # controller's converged knobs (petastorm_tpu.planner).  Once
            # per reader, best-effort - teardown must never fail on it.
            self._profile_written = True
            try:
                from petastorm_tpu import planner as _planner

                _planner.write_profile(self)
            except Exception:  # noqa: BLE001 - teardown is best-effort
                logger.debug("flight-profile write failed", exc_info=True)
        if self.warm_cache is not None:
            # final fold BEFORE the observability close latches the final
            # telemetry snapshot: a short run's cache.* activity must not
            # be lost to the 0.5s publish gate
            try:
                self.warm_cache.publish_telemetry()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                logger.debug("final warm-cache publish failed", exc_info=True)
        if self.autotune is not None:
            # controller before executor: a tuning tick landing mid-close
            # must not resize a stopped pool (a process-pool grow would
            # spawn a worker nobody joins)
            try:
                self.autotune.stop()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                logger.debug("autotune stop failed", exc_info=True)
        self._ventilator.stop()
        self._executor.stop()
        self._close_observability()

    # -- live host<->device decode split (decode_placement='auto') ------------

    @property
    def decode_split(self) -> Optional[str]:
        """'host' | 'device' for the live-split fields, or None when no
        field uses ``decode_placement='auto'``."""
        if self._decode_split_cell is None:
            return None
        return "device" if int(self._decode_split_cell.value) else "host"

    def set_decode_split(self, mode) -> int:
        """Move the live host<->device decode split (docs/operations.md
        "Decode tuning").

        ``mode``: ``'host'``/``0`` = workers ship fully-decoded pixels
        (libjpeg on host), ``'device'``/``1`` = workers ship entropy-decoded
        coefficient planes and the JaxDataLoader runs dequant+IDCT on-chip.
        Takes effect per ROWGROUP: rowgroups already decoded keep their form
        (the loader assembles the two forms separately, so in-flight batches
        stay correct).  This is the autotune controller's ``decode_split``
        knob; safe to call directly while the reader runs.  Returns the new
        value (0/1).
        """
        if self._decode_split_cell is None:
            raise PetastormTpuError(
                "set_decode_split needs a decode_placement='auto' field"
                " (no live-split field on this reader)")
        if mode in ("host", 0, False):
            value = 0
        elif mode in ("device", 1, True):
            value = 1
        else:
            raise PetastormTpuError(
                f"decode split mode must be 'host'/0 or 'device'/1,"
                f" got {mode!r}")
        self._decode_split_cell.value = value
        if self.telemetry.enabled:
            self.telemetry.gauge("decode.split").set(value)
            self.telemetry.counter(
                f"decode.split_to_{'device' if value else 'host'}").add(1)
        return value

    def _close_observability(self) -> None:
        """Latch the final snapshot and stop the sampler + metrics endpoint;
        idempotent (every close path and the constructor-failure path funnel
        here)."""
        if self._observability_closed:
            return
        self._observability_closed = True
        if self.autotune is not None:
            # controller before sampler: a tuning thread must not decide
            # from a stopped sampler's stale series
            try:
                self.autotune.stop()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                logger.debug("autotune stop failed", exc_info=True)
        if self.sampler is not None:
            try:  # flush the trailing partial interval into the series
                self.sampler.sample_now()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                logger.debug("final sample failed", exc_info=True)
            self.sampler.stop()
        if self.telemetry.enabled and self._final_snapshot is None:
            try:
                self._final_snapshot = self.telemetry.snapshot()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                logger.debug("final snapshot failed", exc_info=True)
        if self.metrics_server is not None:
            self.metrics_server.stop()

    def join(self) -> None:
        """Wait for the pool workers and ventilator to exit (after stop()).

        After a stall abort the wait is bounded: the executor abandons any
        worker still wedged inside user code (daemon threads) instead of
        trading the iteration hang the abort just broke for a close hang.
        Bounded-join support is detected from the executor's signature, not
        by catching TypeError around the call - a real TypeError raised
        INSIDE a bounded join must propagate, not silently degrade into an
        unbounded re-join.
        """
        self._ventilator.join()
        if self._stall_aborted:
            join_params = inspect.signature(self._executor.join).parameters
            if "timeout" in join_params:
                self._executor.join(timeout=5.0)
                return
        self._executor.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        self.join()

    @property
    def diagnostics(self) -> dict:
        """Observability snapshot: items consumed/expected, epoch position,
        pool queue depths, and the fault ledger (skipped/quarantined
        rowgroups, requeued items)."""
        diag = {**self._executor.diagnostics,
                "items_per_epoch": self._ventilator.items_per_epoch,
                "consumed_items": self._consumed_items,
                "expected_items": self._expected_items,
                # the stream certificate (seed-stable under
                # deterministic='seed'; see docs/operations.md
                # "Reproducibility" for capturing and diffing it)
                "deterministic": self.deterministic,
                "stream_digest": self._digest.summary(),
                "reorder_held": len(self._det_held),
                "skipped_rowgroups": len(self._quarantine),
                # bounded tail: diagnostics is interpolated into stall
                # WARNINGs, and a long degraded run must not turn every log
                # line into the full ledger (quarantined_rowgroups property
                # has it all; the count above is always exact)
                "quarantined_rowgroups": list(self._quarantine[-20:])}
        # native-plane availability: a silent fallback to the slow per-cell
        # decode (missing .so) must be visible here, not just in one log line
        from petastorm_tpu.native import image as _native_image
        from petastorm_tpu.native import is_available as _shm_available
        from petastorm_tpu.native import \
            transport_availability as _shm_availability

        diag["native"] = {"image_decode": _native_image.available(),
                          "shm_arena": _shm_available(),
                          # WHY the zero-copy plane is (un)available - a
                          # dark shm fast path (py<3.12, missing .so) must
                          # be readable here, not inferred from a slow bench
                          "shm_transport": _shm_availability(),
                          "build_command": _native_image.BUILD_COMMAND}
        if self._decode_split_cell is not None:
            diag["decode_split"] = self.decode_split
        if self.warm_cache is not None:
            # host-wide tier state: hit/miss/eviction ledger, resident bytes
            # vs target, entry count (petastorm_tpu.cache_shared)
            try:
                diag["cache"] = self.warm_cache.stats()
            except Exception:  # noqa: BLE001 - diagnostics must not raise
                logger.debug("warm-cache stats failed", exc_info=True)
        if self.circuit_breaker is not None:
            diag["circuit_breaker"] = self.circuit_breaker.snapshot()
        if self.autotune is not None:
            # knob values + bounded decision log (what the tuner did and why)
            diag["autotune"] = self.autotune.diagnostics
        if self.planner is not None:
            # the static planner's verdict: planned knob values with per-knob
            # provenance (profile / metadata / default / pinned) plus the
            # footer summary and profile path it planned from
            try:
                diag["planner"] = self.planner.to_dict()
            except Exception:  # noqa: BLE001 - diagnostics must not raise
                logger.debug("planner verdict serialization failed",
                             exc_info=True)
        if self._flight_record is not None:
            # the sampled series + trace tail leading into a terminal failure
            diag["flight_recorder"] = self._flight_record
        if self._final_snapshot is not None:
            # full telemetry snapshot latched at close, on every close path
            diag["telemetry"] = self._final_snapshot
        if self.metrics_server is not None:
            diag["metrics_port"] = self.metrics_server.port
        return diag

    @property
    def quarantined_rowgroups(self) -> list:
        """Skipped-work-item ledger under an ``on_error`` skip policy: one
        dict per skip (ordinal, path, row_group, kind, exc_type, error)."""
        return list(self._quarantine)

    @property
    def declared_geometries(self) -> dict:
        """{field: [shape tuples]} stamped at write/copy time, or {} - the
        dataset-level geometry contract the jax loader's 'device-mixed'
        decode uses to bound its compile count (etl.metadata)."""
        from petastorm_tpu.etl.metadata import declared_geometries

        return declared_geometries(self.dataset_info)
