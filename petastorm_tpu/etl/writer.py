"""Spark-free dataset writer.

Reference parity: ``materialize_dataset`` (petastorm/etl/dataset_metadata.py:53-133)
which (a) pre-configures the writer (rowgroup size), (b) lets the user write parquet,
(c) stamps schema + per-file rowgroup counts into ``_common_metadata`` and validates
readability (dataset_metadata.py:118-131).  The reference's row encoding is
``dict_to_spark_row`` on Spark executors (unischema.py:356-403).

Here the default writer is pyarrow-native (no JVM): ``write_dataset`` encodes rows
columnar-batch-at-a-time and writes parquet directly; ``materialize_dataset`` is kept
as a context manager for interop flows (user writes parquet by any means - pandas,
polars, Spark-over-parquet - and we stamp metadata on exit).  Distributed writes on a
TPU pod: every host calls ``write_dataset`` with a distinct ``file_prefix`` (e.g.
``f"part-{jax.process_index()}"``) into the same directory, then exactly one host
calls ``stamp_dataset_metadata`` - coordination is the caller's (or
petastorm_tpu.parallel's) job, not a JVM's.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import logging
import posixpath
import uuid
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Union

import numpy as np
import pyarrow as pa
import pyarrow.fs as pafs
import pyarrow.parquet as pq

from petastorm_tpu.errors import MetadataError, SchemaError
from petastorm_tpu.etl.metadata import (GEOMETRIES_METADATA_KEY,
                                        ROW_GROUPS_METADATA_KEY, _is_data_file,
                                        _read_kv_metadata,
                                        collect_row_group_counts, hive_partition_segment,
                                        open_dataset, write_metadata_file)
from petastorm_tpu.fs import get_filesystem_and_path
from petastorm_tpu.schema import SCHEMA_METADATA_KEY, Schema, insert_explicit_nulls

logger = logging.getLogger(__name__)

DEFAULT_ROW_GROUP_SIZE_MB = 32  # reference default: row_group_size_mb (dataset_metadata.py:62)


def default_compression(schema: Schema, exclude: Optional[set] = None
                        ) -> Dict[str, str]:
    """Per-column parquet codecs: snappy, but UNCOMPRESSED for fields whose
    codec already emits entropy-coded bytes (``Codec.precompressed``)."""
    exclude = exclude or set()
    return {f.name: ("NONE" if getattr(f.codec, "precompressed", False)
                     else "SNAPPY")
            for f in schema if f.name not in exclude}


def _encode_chunk(schema: Schema, file_schema: pa.Schema,
                  rows: List[dict],
                  encode_pool=None) -> pa.RecordBatch:
    """Encode a chunk of row dicts into one arrow RecordBatch (storage types).

    With ``encode_pool`` (a ThreadPoolExecutor), rows encode in parallel: the
    expensive codecs (jpeg/png encode via cv2/libpng, np.savez deflate)
    release the GIL, so write-side materialization scales with host cores.
    Output order is the input order either way.
    """
    prepared = (insert_explicit_nulls(schema, r) for r in rows)
    if encode_pool is not None:
        encoded_rows = list(encode_pool.map(schema.encode_row, prepared))
    else:
        encoded_rows = [schema.encode_row(r) for r in prepared]
    arrays = [pa.array([r[name] for r in encoded_rows], type=file_schema.field(name).type)
              for name in file_schema.names]
    return pa.RecordBatch.from_arrays(arrays, schema=file_schema)


def _estimate_rows_per_group(batch: pa.RecordBatch, target_mb: float) -> int:
    nbytes = max(batch.nbytes, 1)
    per_row = nbytes / max(batch.num_rows, 1)
    return max(1, int(target_mb * 1024 * 1024 / per_row))


def write_dataset(url: str,
                  schema: Schema,
                  rows: Iterable[dict],
                  row_group_size_mb: Optional[float] = None,
                  row_group_size_rows: Optional[int] = None,
                  rows_per_file: Optional[int] = None,
                  partition_by: Sequence[str] = (),
                  file_prefix: str = "part",
                  filesystem: Optional[pafs.FileSystem] = None,
                  storage_options: Optional[dict] = None,
                  stamp_metadata: bool = True,
                  mode: str = "error",
                  compression: Optional[Union[str, Dict[str, str]]] = None,
                  encode_workers: int = 1,
                  geometry_sink: Optional[Dict[str, set]] = None) -> List[str]:
    """Encode + write rows as a petastorm_tpu parquet dataset; returns file paths.

    ``partition_by`` names scalar fields materialized as hive ``key=value``
    directories (values must be str/int/bool-convertible); partitioned fields are
    not duplicated inside the files, matching parquet convention.

    ``mode``: what to do when ``url`` already holds data files - ``"error"``
    (default; silently mixing old and new rows is almost never intended),
    ``"overwrite"`` (delete existing contents first), or ``"append"`` (add new
    part files; the metadata stamp is refreshed to cover old + new).

    ``compression``: parquet codec name, or {column: codec} dict.  Default:
    snappy, except columns whose field codec is ``precompressed`` (PNG/JPEG
    images, compressed ndarrays) are stored UNCOMPRESSED - re-compressing
    entropy-coded bytes saves nothing and costs a decompress pass per read.

    ``encode_workers`` > 1 encodes rows through the codecs on a thread pool
    (jpeg/png/deflate encoding releases the GIL); row and rowgroup order are
    unchanged, so the written dataset is byte-identical either way.

    ``geometry_sink``: coordination hook for multi-writer flows
    (``parallel.distributed_write_dataset``) - the distinct image shapes this
    call observed are ADDED to the given dict ({field: set of shape tuples})
    so a coordinator can merge every writer's set and stamp the combined
    geometry contract; with ``stamp_metadata=True`` the shapes are also
    stamped directly.
    """
    if mode not in ("error", "overwrite", "append"):
        raise ValueError(f"mode must be 'error', 'overwrite' or 'append',"
                         f" got {mode!r}")
    if row_group_size_mb is None and row_group_size_rows is None:
        row_group_size_mb = DEFAULT_ROW_GROUP_SIZE_MB
    for pcol in partition_by:
        if pcol not in schema:
            raise SchemaError(f"partition_by field {pcol!r} not in schema")
        if schema[pcol].shape != ():
            raise SchemaError(f"partition_by field {pcol!r} must be scalar")

    fs, root = get_filesystem_and_path(url, storage_options, filesystem)
    if mode != "append" and fs.get_file_info(root).type == pafs.FileType.Directory:
        existing = [f.path for f in fs.get_file_info(
                        pafs.FileSelector(root, recursive=True))
                    if f.type == pafs.FileType.File and _is_data_file(f.path)]
        if existing and mode == "error":
            raise SchemaError(
                f"Dataset path {url!r} already contains {len(existing)} data"
                " file(s); pass mode='overwrite' to replace or mode='append'"
                " to add to it")
        if existing:
            fs.delete_dir_contents(root)
    fs.create_dir(root, recursive=True)

    storage = schema.as_arrow_schema()
    file_schema = pa.schema([storage.field(f.name) for f in schema
                             if f.name not in set(partition_by)],
                            metadata={SCHEMA_METADATA_KEY: schema.to_json()})
    if compression is None:
        compression = default_compression(schema, exclude=set(partition_by))

    writers: Dict[str, pq.ParquetWriter] = {}
    files: List[str] = []
    rows_written: Dict[str, int] = {}
    rows_per_group = row_group_size_rows

    def _writer_for(partition_values: tuple) -> pq.ParquetWriter:
        key = "/".join(hive_partition_segment(k, v) for k, v in partition_values)
        if key not in writers:
            subdir = posixpath.join(root, key) if key else root
            fs.create_dir(subdir, recursive=True)
            fname = f"{file_prefix}-{len(files):05d}-{uuid.uuid4().hex[:8]}.parquet"
            path = posixpath.join(subdir, fname)
            # page checksums are the storage-integrity layer: the image codec's
            # native decoder skips in-stream PNG CRCs, so corruption detection
            # belongs here (verified on read via verify_checksums=True)
            writers[key] = pq.ParquetWriter(path, file_schema, filesystem=fs,
                                            compression=compression,
                                            write_page_checksum=True)
            files.append(path)
            rows_written[key] = 0
        return writers[key]

    def _delete_files_best_effort(fs_, paths):
        for path in paths:
            try:
                fs_.delete_file(path)
            except Exception:  # noqa: BLE001 - already failing
                logger.warning("could not delete partial file %s after failed"
                               " write", path, exc_info=True)

    # dataset-level geometry contract: record the distinct image shapes of
    # variable-shape CompressedImageCodec fields while the rows stream by, so
    # readers know EVERY geometry up front (the jax loader's 'device-mixed'
    # canvases must hold them all)
    from petastorm_tpu.codecs import CompressedImageCodec

    geom_fields = [f.name for f in schema
                   if isinstance(f.codec, CompressedImageCodec)
                   and any(d is None for d in f.shape)]
    geom_seen: Dict[str, set] = (geometry_sink if geometry_sink is not None
                                 else {})
    for name in geom_fields:
        geom_seen.setdefault(name, set())

    _ESTIMATE_CHUNK = 1024  # rows encoded to estimate bytes/row for MB-based sizing
    pending: Dict[tuple, List[dict]] = {}

    def _flush(pv: tuple, final: bool) -> None:
        """Write full rowgroups from the partition buffer; keep the remainder.

        Buffering per partition (not per encode-chunk) is what prevents runt
        rowgroups when rows interleave across partitions.
        """
        nonlocal rows_per_group
        buf = pending.get(pv, [])
        threshold = rows_per_group if rows_per_group is not None else _ESTIMATE_CHUNK
        while buf and (final or len(buf) >= threshold):
            chunk, buf = buf[:threshold], buf[threshold:]
            batch = _encode_chunk(schema, file_schema, chunk,
                                  encode_pool=encode_pool)
            if rows_per_group is None:
                rows_per_group = _estimate_rows_per_group(batch, row_group_size_mb)
                threshold = rows_per_group
            writer = _writer_for(pv)
            # write_table splits into ceil(n/rows_per_group) rowgroups itself,
            # which only matters for the estimate chunk exceeding the target
            writer.write_table(pa.Table.from_batches([batch]),
                               row_group_size=rows_per_group)
            key = "/".join(hive_partition_segment(k, v) for k, v in pv)
            rows_written[key] += batch.num_rows
            if rows_per_file and rows_written[key] >= rows_per_file:
                writers.pop(key).close()
                rows_written[key] = 0
        pending[pv] = buf

    encode_pool = None
    if encode_workers > 1:
        from concurrent.futures import ThreadPoolExecutor

        encode_pool = ThreadPoolExecutor(max_workers=encode_workers,
                                         thread_name_prefix="pst-encode")
    failed = False
    try:
        for r in rows:
            for k in partition_by:
                if r.get(k) is None:
                    raise SchemaError(f"Row is missing a value for partition field {k!r}"
                                      " (partition values must be non-null)")
            pv = tuple((k, str(r[k])) for k in partition_by)
            for name in geom_fields:
                v = r.get(name)
                if v is not None:
                    geom_seen[name].add(tuple(np.asarray(v).shape))
            pending.setdefault(pv, []).append(r)
            if len(pending[pv]) >= (rows_per_group or _ESTIMATE_CHUNK):
                _flush(pv, final=False)
        for pv in list(pending):
            _flush(pv, final=True)
    except BaseException:
        failed = True
        raise
    finally:
        if encode_pool is not None:
            encode_pool.shutdown(wait=True)
        if failed:
            # best-effort close so output streams/multipart uploads are not
            # leaked when encoding or the caller's row generator raised (the
            # happy path closes below, where a footer-write failure must
            # still raise loudly)
            for w in writers.values():
                try:
                    w.close()
                except Exception:  # noqa: BLE001 - already failing
                    logger.warning("could not close parquet writer after"
                                   " failed write", exc_info=True)
            # close() wrote footers, so the debris now parses as VALID parquet
            # that a later mode='append' run or metadata stamp would silently
            # adopt as complete data - delete what this failed call produced
            _delete_files_best_effort(fs, files)

    close_exc = None
    for w in writers.values():
        try:
            w.close()
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            # keep closing the REST: an unclosed writer's stream could flush
            # its footer later (GC/multipart commit) and resurrect a valid
            # parquet file after the cleanup below deletes it
            if close_exc is None:
                close_exc = exc
    if close_exc is not None:
        # a footer flush failed (ENOSPC, upload error): earlier writers in
        # this loop closed fine, so their files parse as complete parquet -
        # the whole call failed, none of its output may survive to be adopted
        _delete_files_best_effort(fs, files)
        raise close_exc
    if not files:
        logger.warning("write_dataset(%s): no rows were written; dataset left empty",
                       url)
        return []
    if stamp_metadata:
        stamp_dataset_metadata(url, schema, filesystem=fs,
                               geometries={n: s for n, s in geom_seen.items()
                                           if s} or None)
    return files


def stamp_dataset_metadata(url: str, schema: Optional[Schema] = None,
                           filesystem: Optional[pafs.FileSystem] = None,
                           storage_options: Optional[dict] = None,
                           validate: bool = True,
                           geometries: Optional[Dict[str, Iterable]] = None,
                           merge_geometries: bool = True) -> None:
    """Write/refresh ``_common_metadata``: schema JSON + per-file rowgroup counts.

    ``geometries``: {field: iterable of image shape tuples} to stamp as the
    dataset-level geometry contract (see ``etl.metadata.declared_geometries``).
    With ``merge_geometries=True`` (default) they are unioned with any
    already-stamped shapes - right for ``mode='append'`` writes, which see
    only their own rows.  Pass ``merge_geometries=False`` when the given set
    is authoritative for the WHOLE dataset (a full rescan:
    ``petastorm-tpu-generate-metadata --scan-geometries``), so stale
    geometries from rewritten files actually disappear.

    Reference: the post-write half of ``materialize_dataset``
    (dataset_metadata.py:113-131) and the standalone regenerator CLI
    (etl/petastorm_generate_metadata.py).
    """
    fs, root = get_filesystem_and_path(url, storage_options, filesystem)
    selector = pafs.FileSelector(root, recursive=True)
    files = sorted(f.path for f in fs.get_file_info(selector)
                   if f.type == pafs.FileType.File and _is_data_file(f.path))
    if not files:
        raise MetadataError(f"No data files under {url!r} to stamp metadata for")
    counts = collect_row_group_counts(fs, root, files)
    with fs.open_input_file(files[0]) as f:
        arrow_schema = pq.ParquetFile(f).schema_arrow
    if schema is None:
        file_kv = arrow_schema.metadata or {}
        if SCHEMA_METADATA_KEY not in file_kv:
            raise MetadataError(
                "No schema given and data files carry no petastorm-tpu schema;"
                " pass schema= explicitly")
        schema = Schema.from_json(file_kv[SCHEMA_METADATA_KEY])
    kv = {
        SCHEMA_METADATA_KEY: schema.to_json().encode(),
        ROW_GROUPS_METADATA_KEY: json.dumps({"files": counts}).encode(),
    }
    # an EMPTY dict with merge_geometries=False is meaningful: an authoritative
    # rescan found no image geometries, so the stamped contract must become
    # empty (write_metadata_file's KV merge would otherwise preserve the stale
    # key and the "REPLACE" semantics of --scan-geometries would silently fail)
    if geometries or (geometries is not None and not merge_geometries):
        merged: Dict[str, set] = {n: {tuple(int(d) for d in s) for s in shapes}
                                  for n, shapes in geometries.items()}
        existing_raw = (_read_kv_metadata(fs, root).get(GEOMETRIES_METADATA_KEY)
                        if merge_geometries else None)
        if existing_raw:
            try:
                for n, shapes in json.loads(existing_raw).items():
                    merged.setdefault(n, set()).update(
                        tuple(int(d) for d in s) for s in shapes)
            except (ValueError, TypeError):
                logger.warning("discarding unparseable stamped geometry"
                               " metadata during re-stamp")
        kv[GEOMETRIES_METADATA_KEY] = json.dumps(
            {n: sorted(list(s) for s in shapes)
             for n, shapes in merged.items()}).encode()
    write_metadata_file(fs, root, arrow_schema, kv)
    if validate:
        info = open_dataset(url, filesystem=fs, require_stored_schema=True)
        if not info.row_groups:
            raise MetadataError(f"Validation failed: no rowgroups visible at {url!r}")


@contextlib.contextmanager
def materialize_dataset(url: str, schema: Schema,
                        filesystem: Optional[pafs.FileSystem] = None,
                        storage_options: Optional[dict] = None) -> Iterator[None]:
    """Context manager: user writes parquet under ``url`` inside the block (by any
    engine), metadata is stamped + validated on exit.

    Reference: ``materialize_dataset`` (dataset_metadata.py:53-133), minus the JVM.
    Encoded cell values must follow the schema's storage types - use
    ``schema.encode_row`` (the ``dict_to_spark_row`` equivalent) on each row.
    """
    yield
    stamp_dataset_metadata(url, schema, filesystem=filesystem,
                           storage_options=storage_options)
