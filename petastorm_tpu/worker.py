"""Rowgroup decode worker: parquet rowgroup -> decoded ColumnBatch.

Reference parity: petastorm/py_dict_reader_worker.py (row path: per-row dict decode,
predicate split-read at 188-252, cache lookup at 155-163) and
petastorm/arrow_reader_worker.py (batch path: columnar, pandas predicates at
224-283, whole-rowgroup transform at 190-222).

One worker serves both paths here because decode is columnar either way; the row/
batch distinction is purely how the Reader unpacks the ColumnBatch.  The predicate
split-read optimization is kept: predicate columns are read+decoded first, the
surviving-row mask filters the *arrow* table of the remaining columns before their
(expensive) decode runs.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
from typing import Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.fs as pafs
import pyarrow.parquet as pq

from petastorm_tpu.batch import ColumnBatch
from petastorm_tpu.cache import CacheBase, NullCache
from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.fs import FilesystemFactory
from petastorm_tpu.plan import WorkItem
from petastorm_tpu.schema import Schema
from petastorm_tpu.telemetry import NULL_CONTEXT as _NULL_CONTEXT
from petastorm_tpu.telemetry import resolve as _resolve_telemetry
from petastorm_tpu.transform import TransformSpec

logger = logging.getLogger(__name__)

_MAX_OPEN_FILES = 8

#: cache-key stage tag for post-transform entries.  Versioned like the
#: rawcoef tags: bump it if the cached post-transform form ever changes, so
#: a warm persistent tier from an older build can never poison the pipeline.
_TRANSFORM_STAGE = "xform1"


class RowGroupDecoderWorker:
    """Picklable worker factory (pool.WorkerFactory protocol).

    ``__call__`` runs once in the worker thread/process and returns the hot
    ``process(WorkItem) -> ColumnBatch`` closure with lazily-opened file handles
    (reference opens the dataset lazily per worker, py_dict_reader_worker.py:134-138).
    """

    def __init__(self,
                 fs_factory: FilesystemFactory,
                 schema: Schema,
                 read_fields: Sequence[str],
                 predicate=None,
                 transform: Optional[TransformSpec] = None,
                 cache: Optional[CacheBase] = None,
                 ngram=None,
                 ngram_schema: Optional[Schema] = None,
                 verify_checksums: bool = False,
                 raw_fields: Sequence[str] = (),
                 mixed_raw_fields: Sequence[str] = (),
                 retry_policy=None,
                 circuit_breaker=None,
                 telemetry=None,
                 decode_threads: int = 1,
                 decode_roi: Optional[Dict[str, tuple]] = None,
                 split_fields: Sequence[str] = (),
                 decode_split=None,
                 transform_cache_info=None):
        self._fs_factory = fs_factory
        self._schema = schema
        self._read_fields = list(read_fields)
        self._predicate = predicate
        self._transform = transform
        self._cache = cache or NullCache()
        self._cache_prefix = hashlib.md5(fs_factory.url.encode()).hexdigest()
        self._ngram = ngram
        self._ngram_schema = ngram_schema or schema
        self._verify_checksums = verify_checksums
        #: petastorm_tpu.retry.RetryPolicy (or None): transient read failures
        #: on remote stores are retried with the cached file handle dropped
        self._retry_policy = retry_policy
        #: petastorm_tpu.retry.CircuitBreaker (or None), shared across this
        #: reader's workers: consecutive transient failures open it and
        #: rowgroup reads fail fast with CircuitOpenError instead of every
        #: worker compounding retry storms against a down store.  Picklable:
        #: spawned process-pool workers each hold their own copy (the
        #: threshold is then per-process - documented in operations.md).
        self._circuit_breaker = circuit_breaker
        #: fields delivered as raw encoded bytes (codec decode skipped) -
        #: decode_placement='device': the jax loader decodes them on-chip
        self._raw_fields = frozenset(raw_fields)
        #: subset shipping the mixed-geometry object wire format
        #: (decode_placement='device-mixed')
        self._mixed_raw_fields = frozenset(mixed_raw_fields)
        #: telemetry recorder; None = not yet resolved (resolution happens in
        #: __call__, in the worker thread/process, so a spawned worker
        #: re-resolves from its own inherited env)
        self._telemetry = (_resolve_telemetry(telemetry)
                           if telemetry is not None else None)
        #: internal fan-out of the native batched image decode (this worker's
        #: share of the host's cores; the pool provides inter-worker
        #: parallelism, this provides intra-batch parallelism on top)
        self._decode_threads = max(1, int(decode_threads))
        #: field -> ROI spec ((y, x, h, w) | ('center', h, w) |
        #: ('random', h, w)): partial decode of image columns - only the
        #: kept crop window is decoded (make_reader(decode_roi=...))
        self._decode_roi = dict(decode_roi or {})
        #: fields under the LIVE host<->device decode split
        #: (decode_placement='auto'): each rowgroup consults the shared
        #: ``decode_split`` cell when it decodes - 0 ships pixels (full
        #: libjpeg decode here), 1 ships coefficient planes (entropy-only
        #: here, IDCT on the device).  The autotune controller moves the
        #: cell live; thread pools share the object, spawned process pools
        #: inherit the multiprocessing.Value through Process args.
        self._split_fields = frozenset(split_fields)
        self._decode_split = decode_split
        #: arena batch-slot decode is only safe when the cache never retains
        #: REFERENCES to the decoded batch beyond delivery (a cached arena
        #: view would dangle after the consumer frees the slot).  Every
        #: in-tree cache stores copies / serialized bytes and declares so
        #: (CacheBase.retains_value_references) - notably the shared warm
        #: tier, which composes with slot decode instead of disabling it;
        #: unknown third-party caches keep the conservative default.
        self._allow_batch_slots = not getattr(
            self._cache, "retains_value_references", True)
        self._cache_is_null = isinstance(self._cache, NullCache)
        from petastorm_tpu import transform as _transform_mod
        from petastorm_tpu.transform import log_output_cache_disabled

        # ONE analysis walk yields both halves (it md5s bytecode + any
        # captured arrays - too heavy to repeat, and _cache_key is on the
        # per-item hot path so the signature is memoized here); make_reader
        # precomputes the triple (the planner's schema hash shares it) and
        # passes it in, direct constructions compute their own:
        #: content signature (closure cells + read globals folded)
        #: post-transform output caching (MinatoLoader-style, docs/
        #: operations.md "Transform caching & the pipeline planner"): when
        #: the transform is provably deterministic the cache stores its
        #: OUTPUT under the decode key + a stage tag, so warm epochs skip
        #: decode AND transform.  Ngram readers are excluded (windows form
        #: after the transform with slice-dependent anchors - small win,
        #: wide blast radius), as is anything uncertain about determinism.
        if transform_cache_info is None:
            transform_cache_info = _transform_mod.transform_cache_info(
                self._transform)
        self._transform_signature, cacheable, reason = transform_cache_info
        self._transform_output_cached = False
        if (self._transform is not None and not self._cache_is_null
                and ngram is None):
            if cacheable:
                self._transform_output_cached = True
                logger.info(
                    "post-transform output caching armed (%s; signature %s,"
                    " stage tag %r)", reason, self._transform_signature,
                    _TRANSFORM_STAGE)
            else:
                log_output_cache_disabled(self._transform, reason,
                                          self._transform_signature)
        #: per-file (size, mtime) fingerprints for cache keys - a dataset
        #: rewritten in place must never serve stale warm-tier entries.
        #: Plain dict: GIL-atomic set; a racing duplicate stat is benign.
        self._file_fps: Dict[str, str] = {}

    # -- factory protocol -----------------------------------------------------

    def __getstate__(self):
        # a live Telemetry holds locks and a trace buffer - not picklable,
        # and not meaningful across a process boundary anyway: the spawned
        # worker re-resolves from PETASTORM_TPU_TELEMETRY (inherited env)
        state = dict(self.__dict__)
        state["_telemetry"] = None
        return state

    def __call__(self):
        if self._telemetry is None:
            self._telemetry = _resolve_telemetry(None)
        tele = self._telemetry
        fs = self._fs_factory()
        # path -> (ParquetFile, column-name set, WindowedFile | None); the
        # column set is cached because schema_arrow reconstruction is
        # measurable on the per-item hot path
        open_files: Dict[str, tuple] = {}

        def _parquet_file(path: str) -> tuple:
            entry = open_files.get(path)
            if entry is None:
                if len(open_files) >= _MAX_OPEN_FILES:
                    oldest = next(iter(open_files))
                    open_files.pop(oldest)[0].close()
                local = isinstance(fs, pafs.LocalFileSystem)
                window = None
                if local:
                    # memory-map local files: rowgroup reads skip a buffered
                    # copy (~30% faster on image-sized groups); arrow buffers
                    # hold a reference to the map, and a deleted-under-us file
                    # keeps its inode alive on linux, so lifetime is safe
                    source = pa.memory_map(path)
                else:
                    # remote stores: wrap the file in a WindowedFile so each
                    # rowgroup's column span is fetched in ONE ranged read
                    # (io_window; kills the multi-read amplification of
                    # arrow's pre_buffer) with raw reads counted for the
                    # io.reads_per_rowgroup telemetry.  pre_buffer stays on
                    # as the fallback coalescer for spans the window guard
                    # rejects - its ranged reads land inside the window when
                    # one is armed, so the two never double-fetch.
                    from petastorm_tpu.io_window import WindowedFile

                    window = WindowedFile(fs.open_input_file(path))
                    source = pa.PythonFile(window, mode="r")
                pf = pq.ParquetFile(source, pre_buffer=not local,
                                    page_checksum_verification=self._verify_checksums)
                entry = (pf, set(pf.schema_arrow.names), window)
                open_files[path] = entry
            return entry

        def process(item) -> ColumnBatch:
            from petastorm_tpu.pool import VentilatedItem
            from petastorm_tpu.retry import retry_call

            ordinal = None
            if isinstance(item, VentilatedItem):
                ordinal, item = item.ordinal, item.item

            def drop_handle(_exc):
                # the cached ParquetFile (its buffered stream/connection) may
                # be poisoned by the failure; reopen on the next attempt
                entry = open_files.pop(item.row_group.path, None)
                if entry is not None:
                    try:
                        entry[0].close()
                    except Exception:  # noqa: BLE001 - already failing
                        pass

            stats_before = None
            if tele.enabled:
                from petastorm_tpu.native import image as native_image

                stats_before = native_image.decode_stats()
            batch = retry_call(
                lambda: self._process(_parquet_file, item, fs, ordinal),
                self._retry_policy,
                what=f"rowgroup {item.row_group.path}"
                     f"#{item.row_group.row_group}",
                on_retry=drop_handle,
                telemetry=tele,
                breaker=self._circuit_breaker)
            if tele.enabled:
                tele.counter("worker.rowgroups_decoded").add(1)
                tele.counter("worker.rows_decoded").add(batch.num_rows)
                if stats_before is not None:
                    # fold the native decoder's process-local counters into
                    # telemetry as decode.* series (batched/ROI/coefficient
                    # call + image counts) - the observable proof the batched
                    # path is actually taken.  NOTE: per-worker counts from a
                    # thread pool land in the shared registry; a spawned
                    # process pool's stay process-local (same caveat as the
                    # worker stage spans).
                    from petastorm_tpu.native import image as native_image

                    after = native_image.decode_stats()
                    for key, value in after.items():
                        delta = value - stats_before.get(key, 0)
                        if delta:
                            tele.counter(f"decode.{key}").add(delta)
            # ordinal rides the batch so the consumer can track the exact
            # contiguous consumed prefix (resume correctness under pools
            # that complete items out of ventilation order).  Shallow copy:
            # a cached batch object may be delivered again next epoch with a
            # different ordinal, so the cached instance must stay unmarked.
            return dataclasses.replace(batch, ordinal=ordinal)

        return process

    # -- hot path -------------------------------------------------------------

    def _process(self, parquet_file, item: WorkItem, fs=None,
                 ordinal: Optional[int] = None) -> ColumnBatch:
        anchor = None
        row_range = None
        if self._ngram is not None:
            lo, hi = item.row_slice()
            if self._ngram.timestamp_overlap:
                # row-drop slices: read the slice plus length-1 lookahead rows
                # and anchor window starts inside the slice (reference
                # borrowing, py_dict_reader_worker.py:254-274).  Assumes
                # rowgroups are stored timestamp-sorted, as the reference does.
                row_range = (lo, min(hi + self._ngram.length - 1,
                                     item.row_group.num_rows))
                anchor = (0, hi - lo)
            else:
                # non-overlap selection is a GLOBAL greedy property of the
                # rowgroup; partitions must all see the full group or they
                # would pick overlapping windows near slice boundaries
                anchor = (lo, hi)
            load_item = WorkItem(item.row_group)
        else:
            load_item = item
        tele = self._telemetry
        traced = tele is not None and tele.enabled
        decode_stage = (tele.stage("decode", path=item.row_group.path,
                                   rowgroup=item.row_group.row_group,
                                   ordinal=ordinal)
                        if traced else _NULL_CONTEXT)
        if self._predicate is None:
            # key covers the rows ACTUALLY loaded (incl. ngram lookahead), so
            # readers with different ngram lengths never share an entry
            span = row_range if row_range is not None else load_item.row_slice()
            if self._transform_output_cached:
                # the cached value is the TRANSFORM's output, keyed by the
                # decode key + a stage tag: decode-only entries (other jobs,
                # or this transform with caching off) live under the
                # untagged key and never cross-serve.  Stage spans live
                # INSIDE the fill, so a warm hit records zero decode/
                # transform samples - the observable proof both ran nowhere.
                key = self._cache_key(load_item, span, fs,
                                      stage=_TRANSFORM_STAGE)
                filled: list = []

                def _decode_and_transform() -> ColumnBatch:
                    filled.append(True)
                    with (tele.stage("decode", path=item.row_group.path,
                                     rowgroup=item.row_group.row_group,
                                     ordinal=ordinal)
                          if traced else _NULL_CONTEXT):
                        fresh = self._load(parquet_file, load_item,
                                           self._read_fields,
                                           row_range=row_range,
                                           ordinal=ordinal)
                    if fresh.num_rows == 0:
                        # transforms must not see 0-row columns (same
                        # contract as the uncached path below)
                        return fresh
                    with tele.stage("transform") if traced else _NULL_CONTEXT:
                        return self._apply_transform(fresh)

                batch = self._cache.get(key, _decode_and_transform)
                self._note_transform_cache(hit=not filled)
                return batch
            key = self._cache_key(load_item, span, fs)
            with decode_stage:
                batch = self._cache.get(key, lambda: self._load(
                    parquet_file, load_item, self._read_fields,
                    row_range=row_range, ordinal=ordinal))
        else:
            # predicates invalidate rowgroup-level caching (reference
            # py_dict_reader_worker.py:145-150); split-read instead
            with decode_stage:
                batch = self._load_with_predicate(parquet_file, load_item,
                                                  row_range, ordinal)
        if batch.num_rows == 0:
            # fully-masked rowgroup: transforms/ngram must not see 0-row columns
            # (a transform may np.stack/reduce over rows)
            return batch
        if self._transform is None and self._ngram is None:
            return batch
        with tele.stage("transform") if traced else _NULL_CONTEXT:
            batch = self._apply_transform(batch)
            if self._ngram is not None:
                batch = self._ngram.form_windows(self._ngram_schema, batch,
                                                 anchor_range=anchor)
        return batch

    def _file_fingerprint(self, path: str, fs) -> str:
        """(size, mtime) fingerprint of a dataset file, memoized per path -
        the content-address component of shared-tier cache keys (a file
        rewritten in place changes the key, so no reader on the host can be
        served the OLD decode).  '-' for NullCache readers (no key is ever
        used) and when the filesystem cannot answer."""
        if self._cache_is_null or fs is None:
            return "-"
        fp = self._file_fps.get(path)
        if fp is None:
            try:
                info = fs.get_file_info(path)
                fp = f"{info.size}:{info.mtime_ns}"
            except Exception:  # noqa: BLE001 - fingerprint is best-effort
                fp = "?"
            self._file_fps[path] = fp
        return fp

    def _cache_key(self, item: WorkItem, span: tuple, fs=None,
                   stage: str = "decode") -> str:
        start, stop = span
        # 'rawcoef1' versions the stored form of raw/device fields (coefficient
        # plane columns); bump it whenever that format changes, or a warm
        # persistent cache from an older version poisons the pipeline
        tag = (",".join(self._read_fields)
               + "|rawcoef1:" + ",".join(sorted(self._raw_fields))
               + "|mixedcoef1:" + ",".join(sorted(self._mixed_raw_fields))
               # the live decode split and any ROI change the STORED form of
               # a cached batch; key them so a mode flip never serves stale
               + "|split:" + ("-" if self._decode_split is None
                              else str(int(self._decode_split.value)))
               + "|roi:" + repr(sorted(self._decode_roi.items()))
               # under stage='decode' the cached value is the PRE-transform
               # decode, but the key carries the transform signature anyway:
               # the warm tier is shared across jobs, and cross-transform
               # sharing is not worth the blast radius of a signature
               # collision serving job B a batch decoded under job A's
               # settings (ISSUE 7 satellite)
               + "|tf:" + self._transform_signature)
        if stage != "decode":
            # post-transform entries: a distinct stage tag keeps decode-only
            # and decode+transform values apart in ONE shared tier - editing
            # the transform bytecode or flipping `deterministic` mid-job
            # misses cleanly instead of cross-serving (ISSUE 15 satellite)
            tag += f"|stage:{stage}"
        fields_tag = hashlib.md5(tag.encode()).hexdigest()[:8]
        fp = self._file_fingerprint(item.row_group.path, fs)
        return (f"{self._cache_prefix}:{item.row_group.path}:{item.row_group.row_group}"
                f":{start}:{stop}:{fields_tag}:{fp}")

    def _note_transform_cache(self, hit: bool) -> None:
        """Count one post-transform cache event.  The shared tier keeps the
        counters in its cross-process header (visible to every job, published
        by the owning reader as ``cache.transform_*``); per-process caches
        bump this worker's telemetry directly - one path per cache flavor,
        so nothing double-counts."""
        note = getattr(self._cache, "note_transform_event", None)
        if note is not None:
            note(hit)
            return
        tele = self._telemetry
        if tele is not None and tele.enabled:
            tele.counter("cache.transform_hits" if hit
                         else "cache.transform_stores").add(1)

    def _apply_transform(self, batch: ColumnBatch) -> ColumnBatch:
        if self._transform is None:
            return batch
        cols = self._transform(batch.columns)
        nrows = len(next(iter(cols.values()))) if cols else 0
        return ColumnBatch(cols, nrows)

    def _split_to_device(self, name: str) -> bool:
        """Does field ``name`` ship coefficient planes for THIS rowgroup?
        Static 'device'/'device-mixed' placements always do; 'auto' fields
        consult the live decode-split cell (0 = host pixels, 1 = device)."""
        if name not in self._split_fields:
            return True
        cell = self._decode_split
        return cell is None or int(cell.value) != 0

    def _roi_for(self, name: str, item: WorkItem, n: int):
        """Resolve a field's decode-ROI spec to ``(ys, xs, crop_h, crop_w)``
        for this rowgroup's ``n`` rows.  'random' offsets are deterministic
        per (rowgroup, slice): re-reads after requeue/resume decode the same
        crops, so chaos recovery stays exact-multiset."""
        spec = self._decode_roi.get(name)
        if spec is None:
            return None
        field = self._schema[name]
        full_h, full_w = field.shape[:2]
        if spec[0] == "center":
            _, crop_h, crop_w = spec
            return ((full_h - crop_h) // 2, (full_w - crop_w) // 2,
                    crop_h, crop_w)
        if spec[0] == "random":
            _, crop_h, crop_w = spec
            lo, hi = item.row_slice()
            # centralized derivation (petastorm_tpu.seeding): keyed by the
            # work item's MOUNT-INDEPENDENT identity (the dataset-global
            # rowgroup index + row slice - never the filesystem path, whose
            # prefix differs across hosts/mounts; never the ordinal or
            # attempt), so every plan position, requeue, hedge copy,
            # resumed read AND remounted host decodes the same crops -
            # matching the stream certificate's own location independence
            from petastorm_tpu.seeding import seed_stream

            rng = seed_stream(0, 0, "worker.decode_roi",
                              item.row_group.global_index, lo)
            ys = rng.integers(0, full_h - crop_h + 1, n, dtype=np.int32)
            xs = rng.integers(0, full_w - crop_w + 1, n, dtype=np.int32)
            return (ys, xs, crop_h, crop_w)
        y, x, crop_h, crop_w = spec
        return (int(y), int(x), crop_h, crop_w)

    def _load(self, parquet_file, item: WorkItem, fields: Sequence[str],
              mask: Optional[np.ndarray] = None,
              row_range: Optional[tuple] = None,
              ordinal: Optional[int] = None) -> ColumnBatch:
        """Read + slice + (mask) + decode ``fields`` of one rowgroup (no
        transform).  With telemetry on, the Parquet read is the ``read``
        stage, nested in the caller's ``decode``."""
        pf, file_cols, window = parquet_file(item.row_group.path)
        stored = [f for f in fields if f in file_cols]
        virtual = [f for f in fields if f not in file_cols]

        start, stop = row_range if row_range is not None else item.row_slice()
        tele = self._telemetry
        reads_before = window.raw_reads if window is not None else 0
        with (tele.stage("read", ordinal=ordinal,
                         rowgroup=item.row_group.row_group)
              if tele is not None and tele.enabled else _NULL_CONTEXT):
            if window is not None and stored:
                # one ranged read covers the whole rowgroup's needed columns
                # (io_window): every chunk read below lands in the buffer
                from petastorm_tpu.io_window import rowgroup_span

                span = rowgroup_span(pf.metadata, item.row_group.row_group,
                                     stored)
                if span is not None:
                    window.prefetch(span[0], span[1])
            # worker-level parallelism comes from the executor pool; pyarrow's
            # internal thread fan-out per read only adds handoff overhead here
            table = pf.read_row_group(item.row_group.row_group,
                                      columns=stored, use_threads=False)
        if window is not None:
            window.discard_window()  # the decoded table owns the bytes now
            if tele is not None and tele.enabled:
                reads = window.raw_reads - reads_before
                tele.counter("io.read_calls").add(reads)
                tele.counter("io.rowgroups_read").add(1)
                tele.gauge("io.reads_per_rowgroup").set(reads)
        if (start, stop) != (0, table.num_rows):
            table = table.slice(start, stop - start)
        if mask is not None:
            import pyarrow as pa

            table = table.filter(pa.array(mask))
        n = table.num_rows

        from petastorm_tpu.codecs import decode_options

        columns: Dict[str, np.ndarray] = {}
        for name in stored:
            field = self._schema[name]
            chunk = table.column(name).combine_chunks()
            if name in self._raw_fields and self._split_to_device(name):
                # decode_placement='device[-mixed]' (or 'auto' currently
                # split to the device): run the entropy half HERE, in the
                # pool worker; the FLOP-heavy IDCT+upsample+color runs
                # on-chip in the jax loader.  'device' ships fixed-shape
                # coefficient planes (which batch/shuffle/shm-transport like
                # ordinary columns); 'device-mixed' ships per-row object
                # cells grouped by geometry.  The batched entropy decode
                # fans out over this worker's decode threads on top of the
                # pool's parallelism.
                from petastorm_tpu.native.image import (pack_coef_columns,
                                                        pack_coef_columns_mixed)

                pack = (pack_coef_columns_mixed
                        if name in self._mixed_raw_fields else pack_coef_columns)
                columns.update(pack(name, chunk, field,
                                    nthreads=self._decode_threads))
            else:
                # host decode: batched multi-core native image decode with
                # the output allocated straight in an shm batch slot when
                # the process pool armed one (decode-into-slot, zero copy),
                # optionally cropped to the decode ROI
                with decode_options(nthreads=self._decode_threads,
                                    roi=self._roi_for(name, item, n),
                                    batch_slots=self._allow_batch_slots):
                    columns[name] = field.codec.decode_column(field, chunk)
        pvals = dict(item.row_group.partition_values)
        for name in virtual:
            if name not in pvals:
                raise PetastormTpuError(
                    f"Field {name!r} is neither stored in {item.row_group.path!r}"
                    " nor a partition key")
            field = self._schema[name]
            value = pvals[name]
            if field.dtype.kind not in ("U", "S", "O"):
                value = field.dtype.type(value)
                columns[name] = np.full(n, value, dtype=field.dtype)
            else:
                col = np.empty(n, dtype=object)
                col[:] = value
                columns[name] = col
        return ColumnBatch(columns, n)

    def _empty_batch(self) -> ColumnBatch:
        """Zero-row batch carrying ALL read fields with correct dtypes, so
        transforms and ngram formation downstream see a consistent shape."""
        cols = {}
        for name in self._read_fields:
            field = self._schema[name]
            if field.is_fixed_shape and field.dtype.kind not in ("U", "S", "O"):
                cols[name] = np.empty((0,) + field.shape, dtype=field.dtype)
            else:
                cols[name] = np.empty(0, dtype=object)
        return ColumnBatch(cols, 0)

    def _load_with_predicate(self, parquet_file, item: WorkItem,
                             row_range: Optional[tuple] = None,
                             ordinal: Optional[int] = None) -> ColumnBatch:
        pred_fields = list(self._predicate.get_fields())
        missing = [f for f in pred_fields if f not in self._schema]
        if missing:
            raise PetastormTpuError(f"Predicate references unknown fields {missing}")
        # phase 1: predicate columns only (cheap)
        pred_batch = self._load(parquet_file, item, pred_fields,
                                row_range=row_range, ordinal=ordinal)
        mask = np.asarray(self._predicate.do_include_vectorized(pred_batch.columns),
                          dtype=bool)
        tele = self._telemetry
        if tele is not None and tele.enabled:
            # the observable proof of worker-side predicate pushdown: rows
            # masked HERE never reach phase 2, so they cost no payload
            # decode/transform - sequence.rows_filtered counts the drops and
            # worker.rows_decoded counts only the survivors (docs/
            # operations.md "Token pipelines")
            tele.counter("sequence.rows_filtered").add(
                int(mask.size - mask.sum()))
        if not mask.any():
            return self._empty_batch()
        # phase 2: remaining columns, arrow-filtered by the mask BEFORE decode
        remaining = [f for f in self._read_fields if f not in pred_fields]
        if remaining:
            rest = self._load(parquet_file, item, remaining, mask=mask,
                              row_range=row_range, ordinal=ordinal)
            columns = {**{f: pred_batch.columns[f][mask] for f in pred_fields},
                       **rest.columns}
        else:
            columns = {f: pred_batch.columns[f][mask] for f in pred_fields}
        # keep only requested output fields, in schema order (raw/device
        # fields travel as their derived '<name>#...' coefficient columns)
        from petastorm_tpu.native.image import COEF_COLUMN_SEP

        kept: Dict[str, np.ndarray] = {}
        for f in self._read_fields:
            if f in columns:
                kept[f] = columns[f]
            elif f in self._raw_fields:
                for key, col in columns.items():
                    if key.startswith(f + COEF_COLUMN_SEP):
                        kept[key] = col
        return ColumnBatch(kept, int(mask.sum()))
