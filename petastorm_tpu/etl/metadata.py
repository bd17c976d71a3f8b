"""Dataset discovery and metadata: schema + rowgroup enumeration.

Reference parity: petastorm/etl/dataset_metadata.py - schema stamping under a KV key
(dataset_metadata.py:35-36,195-206), per-file rowgroup counts under a second KV key
computed at write time (dataset_metadata.py:209-242), ``load_row_groups`` with three
strategies (dataset_metadata.py:245-350: summary ``_metadata``, cached counts with
path-sorted deterministic ordering, parallel footer reads), and
``infer_or_load_unischema`` (dataset_metadata.py:403-411).

Differences: all KV payloads are JSON (never pickle); discovery uses pyarrow.dataset
(hive partitioning handled by Arrow C++); rowgroup refs carry ``num_rows`` so the
read planner can do row-level accounting (row-drop splits, resumable iterator state)
without re-reading footers.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import posixpath
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple, Union

import pyarrow as pa
import pyarrow.dataset as pads
import pyarrow.fs as pafs
import pyarrow.parquet as pq

from petastorm_tpu.errors import MetadataError
from petastorm_tpu.fs import get_filesystem_and_path_or_paths
from petastorm_tpu.retry import resolve_retry_policy, retry_call
from petastorm_tpu.schema import SCHEMA_METADATA_KEY, Schema

logger = logging.getLogger(__name__)

#: Parquet KV key: JSON ``{"files": {relative_path: [rows_in_rg0, rows_in_rg1, ...]}}``
ROW_GROUPS_METADATA_KEY = b"petastorm-tpu.row_groups_per_file.v1"
#: per-field distinct image shapes, stamped at write/copy time: the
#: DATASET-LEVEL geometry contract (every geometry a reader can possibly
#: encounter is known up front; jax/loader.py 'device-mixed' refuses
#: canvases that cannot hold one).  JSON {field: [[h, w, c], ...]}.
GEOMETRIES_METADATA_KEY = b"petastorm-tpu.image_geometries.v1"
#: Parquet KV key: JSON rowgroup index (petastorm_tpu/etl/indexing.py)
ROWGROUP_INDEX_METADATA_KEY = b"petastorm-tpu.rowgroup_index.v1"

_METADATA_FILENAMES = ("_common_metadata", "_metadata")
_FOOTER_READ_THREADS = 10  # reference uses metadata_nthreads=10 (reader.py:359)


@dataclasses.dataclass(frozen=True)
class RowGroupRef:
    """One unit of read work: a single rowgroup of a single file.

    ``global_index`` is the deterministic ordinal across the whole dataset
    (files path-sorted, rowgroups in file order - reference ordering contract at
    dataset_metadata.py:277-287); sharding and shuffling permute these ordinals.
    """

    path: str                       # absolute path within the dataset's filesystem
    row_group: int                  # ordinal within the file
    num_rows: int
    global_index: int
    partition_values: Tuple[Tuple[str, str], ...] = ()  # hive key=value pairs


class DatasetInfo:
    """Resolved dataset: filesystem, files, schema, rowgroups, KV metadata."""

    def __init__(self, url_or_urls, filesystem: pafs.FileSystem, path_or_paths,
                 files: List[str], arrow_schema: pa.Schema,
                 kv_metadata: Dict[bytes, bytes], row_groups: List[RowGroupRef],
                 stored_schema: Optional[Schema], root_path: str):
        self.url = url_or_urls
        self.filesystem = filesystem
        self.path = path_or_paths
        self.files = files
        self.arrow_schema = arrow_schema
        self.kv_metadata = kv_metadata
        self.row_groups = row_groups
        self.stored_schema = stored_schema
        #: dataset root (above any hive partition directories) - the single place
        #: _common_metadata lives and partition parsing anchors to
        self.root_path = root_path

    @property
    def partition_keys(self) -> List[str]:
        """Hive partition key names, in first-seen rowgroup order."""
        keys = []
        for rg in self.row_groups:
            for k, _ in rg.partition_values:
                if k not in keys:
                    keys.append(k)
        return keys


def hive_partition_segment(key: str, value: str) -> str:
    """``key=value`` path segment with the value percent-encoded (hive/spark
    convention), so '/', '=', '%' in values cannot corrupt the path structure."""
    from urllib.parse import quote

    return f"{key}={quote(str(value), safe='')}"


def parse_hive_partitions(root: str, file_path: str) -> Tuple[Tuple[str, str], ...]:
    """Extract hive ``key=value`` pairs from the path segments under ``root``."""
    from urllib.parse import unquote

    rel = file_path[len(root):].lstrip("/") if file_path.startswith(root) else file_path
    pairs = []
    for seg in rel.split("/")[:-1]:
        if "=" in seg:
            k, _, v = seg.partition("=")
            pairs.append((k, unquote(v)))
    return tuple(pairs)


def _is_data_file(path: str) -> bool:
    name = posixpath.basename(path)
    return not (name.startswith("_") or name.startswith(".") or name.endswith(".crc"))


def _read_kv_metadata(fs: pafs.FileSystem, root: str) -> Dict[bytes, bytes]:
    """KV metadata from ``_common_metadata``/``_metadata`` if present (else {})."""
    for name in _METADATA_FILENAMES:
        mpath = posixpath.join(root, name)
        try:
            info = fs.get_file_info(mpath)
        except (OSError, pa.ArrowInvalid):
            continue
        if info.type == pafs.FileType.File:
            try:
                md = pq.read_metadata(mpath, filesystem=fs).metadata or {}
                return dict(md)
            except (pa.ArrowInvalid, OSError) as exc:
                logger.warning("Failed reading %s: %s", mpath, exc)
    return {}


def _footer_row_groups(fs: pafs.FileSystem, path: str) -> List[int]:
    with fs.open_input_file(path) as f:
        md = pq.ParquetFile(f).metadata
        return [md.row_group(i).num_rows for i in range(md.num_row_groups)]


def _check_legacy_row_group_counts(kv_metadata: Dict[bytes, bytes], root: str,
                                   per_file: Dict[str, List[int]]) -> None:
    """Cross-check footer-derived counts against a legacy petastorm
    ``dataset-toolkit.num_row_groups_per_file.v1`` payload (``{relpath: count}``,
    reference dataset_metadata.py:209-242).  The legacy key stores only rowgroup
    *counts* (not per-rowgroup row counts), so it cannot replace footer reads
    here - but a mismatch means the metadata is stale (files rewritten after
    ``materialize_dataset``), which the reference would silently mis-plan on."""
    from petastorm_tpu.interop import LEGACY_ROW_GROUPS_KEY

    raw = kv_metadata.get(LEGACY_ROW_GROUPS_KEY)
    if not raw:
        return
    try:
        legacy_counts = json.loads(raw)
    except ValueError:
        logger.warning("Corrupt legacy %s payload; ignoring", LEGACY_ROW_GROUPS_KEY)
        return
    for f, rg_rows in per_file.items():
        rel = posixpath.relpath(f, root)
        if rel in legacy_counts and legacy_counts[rel] != len(rg_rows):
            logger.warning(
                "Legacy petastorm metadata is stale for %s: recorded %d rowgroups,"
                " file has %d (dataset rewritten after materialize?)",
                rel, legacy_counts[rel], len(rg_rows))


def load_row_groups(fs: pafs.FileSystem, root: str, files: List[str],
                    kv_metadata: Dict[bytes, bytes],
                    retry_policy=None, telemetry=None) -> List[RowGroupRef]:
    """Enumerate rowgroups for path-sorted ``files``.

    Strategy 1 (fast): cached per-file counts from KV metadata - no footer reads
    (reference dataset_metadata.py:264-287).  Strategy 2: parallel footer reads
    (reference dataset_metadata.py:337-350).
    """
    files = sorted(files)
    counts: Optional[Dict[str, List[int]]] = None
    if ROW_GROUPS_METADATA_KEY in kv_metadata:
        try:
            payload = json.loads(kv_metadata[ROW_GROUPS_METADATA_KEY])
            counts = payload["files"]
        except (ValueError, KeyError) as exc:
            logger.warning("Corrupt %s payload (%s); falling back to footer reads",
                           ROW_GROUPS_METADATA_KEY, exc)
    per_file: Dict[str, List[int]] = {}
    if counts is not None:
        for f in files:
            rel = posixpath.relpath(f, root)
            if rel not in counts:
                logger.warning("File %s missing from cached rowgroup counts; "
                               "falling back to footer reads", rel)
                counts = None
                break
        if counts is not None:
            per_file = {f: counts[posixpath.relpath(f, root)] for f in files}
    if counts is None:
        with ThreadPoolExecutor(max_workers=_FOOTER_READ_THREADS) as pool:
            results = list(pool.map(
                lambda p: retry_call(lambda: _footer_row_groups(fs, p),
                                     retry_policy, what=f"footer of {p}",
                                     telemetry=telemetry),
                files))
        per_file = dict(zip(files, results))
        _check_legacy_row_group_counts(kv_metadata, root, per_file)

    refs: List[RowGroupRef] = []
    for f in files:
        parts = parse_hive_partitions(root, f)
        for rg_idx, nrows in enumerate(per_file[f]):
            refs.append(RowGroupRef(path=f, row_group=rg_idx, num_rows=nrows,
                                    global_index=len(refs), partition_values=parts))
    return refs


def open_dataset(url_or_urls: Union[str, Sequence[str]],
                 storage_options: Optional[dict] = None,
                 filesystem: Optional[pafs.FileSystem] = None,
                 require_stored_schema: bool = False,
                 io_retries="auto", telemetry=None) -> DatasetInfo:
    """Resolve URL(s) -> DatasetInfo with schema, files, rowgroups.

    ``url_or_urls`` may be a dataset directory URL or an explicit list of parquet
    file URLs (reference supports both in make_batch_reader, fs_utils.py:199-228).

    ``io_retries``: transient-failure policy for the listing/KV/footer reads
    (petastorm_tpu.retry) - ``'auto'`` retries on remote filesystems only.
    ``telemetry``: optional recorder; retries are counted as ``io.retries``.
    """
    fs, path_or_paths = get_filesystem_and_path_or_paths(
        url_or_urls, storage_options, filesystem)
    retry_policy = resolve_retry_policy(io_retries, fs)

    def _list(selector):
        return retry_call(lambda: fs.get_file_info(selector), retry_policy,
                          what=f"listing {getattr(selector, 'base_dir', selector)}",
                          telemetry=telemetry)

    if isinstance(path_or_paths, str):
        root = path_or_paths
        info = _list(root)
        if info.type == pafs.FileType.NotFound:
            raise MetadataError(f"Dataset path not found: {url_or_urls!r}")
        if info.type == pafs.FileType.File:
            files = [root]
            root = posixpath.dirname(root)
        else:
            selector = pafs.FileSelector(root, recursive=True)
            files = sorted(f.path for f in _list(selector)
                           if f.type == pafs.FileType.File and _is_data_file(f.path))
    else:
        files = []
        for p in path_or_paths:
            info = _list(p)
            if info.type == pafs.FileType.NotFound:
                raise MetadataError(f"Dataset path not found: {p!r}")
            if info.type == pafs.FileType.File:
                files.append(p)
            else:  # a directory in the list: expand it (reference contract is
                # file lists; accepting dirs beats pyarrow's obscure OSError)
                selector = pafs.FileSelector(p, recursive=True)
                files.extend(f.path for f in _list(selector)
                             if f.type == pafs.FileType.File
                             and _is_data_file(f.path))
        files = sorted(files)
        # dataset root = longest common directory prefix, then strip any trailing
        # hive 'key=value' segments - so partition values survive both for lists
        # spanning partitions AND for a list drawn from a single partition, and
        # _common_metadata at the true dataset root is found
        dirs = [posixpath.dirname(f) for f in files]
        root = posixpath.commonpath(dirs) if len(set(dirs)) > 1 else (dirs[0] if dirs else "")
        while root and "=" in posixpath.basename(root):
            root = posixpath.dirname(root)
    if not files:
        raise MetadataError(f"No parquet data files found under {url_or_urls!r}")

    kv = retry_call(lambda: _read_kv_metadata(fs, root), retry_policy,
                    what=f"metadata of {root}", telemetry=telemetry)
    stored_schema = None
    if SCHEMA_METADATA_KEY in kv:
        stored_schema = Schema.from_json(kv[SCHEMA_METADATA_KEY])
    else:
        # schema may be stamped in data-file footers instead (single-file writes)
        def _file_kv():
            with fs.open_input_file(files[0]) as f:
                return pq.ParquetFile(f).schema_arrow.metadata or {}

        file_kv = retry_call(_file_kv, retry_policy,
                             what=f"schema footer of {files[0]}",
                             telemetry=telemetry)
        if SCHEMA_METADATA_KEY in file_kv:
            stored_schema = Schema.from_json(file_kv[SCHEMA_METADATA_KEY])
            kv = {**file_kv, **kv}
    if stored_schema is None:
        # dataset written by the original Petastorm library: pickled Unischema
        # under dataset-toolkit.unischema.v1 (reference dataset_metadata.py:35-36)
        from petastorm_tpu import interop

        legacy_blob = kv.get(interop.LEGACY_UNISCHEMA_KEY)
        if legacy_blob:
            # an undecodable blob (e.g. user-defined codec subclass outside the
            # interop whitelist) must not break schema-inference consumers like
            # make_batch_reader; they read these datasets fine without it
            try:
                stored_schema = interop.load_legacy_schema(legacy_blob)
                logger.info("Loaded legacy petastorm unischema %r from %s",
                            stored_schema.name, url_or_urls)
            except Exception as exc:
                logger.warning(
                    "Dataset at %s has a legacy petastorm unischema that could"
                    " not be converted (%s); falling back to arrow schema"
                    " inference", url_or_urls, exc)
    if require_stored_schema and stored_schema is None:
        raise MetadataError(
            f"Dataset at {url_or_urls!r} has no petastorm-tpu schema metadata. It was"
            " not created by petastorm_tpu (or metadata was lost); use"
            " make_batch_reader for plain parquet stores, or regenerate metadata with"
            " petastorm_tpu.tools.generate_metadata.")

    dset = retry_call(
        lambda: pads.dataset(files, filesystem=fs, format="parquet",
                             partitioning=pads.HivePartitioning.discover()),
        retry_policy, what=f"dataset schema of {root}", telemetry=telemetry)
    row_groups = load_row_groups(fs, root, files, kv, retry_policy=retry_policy,
                                 telemetry=telemetry)
    return DatasetInfo(url_or_urls, fs, path_or_paths, files, dset.schema, kv,
                       row_groups, stored_schema, root_path=root)


def infer_or_load_schema(info: DatasetInfo) -> Schema:
    """Stored schema if present, else inferred from the arrow schema.

    Reference: ``infer_or_load_unischema`` (dataset_metadata.py:403-411).
    """
    if info.stored_schema is not None:
        return info.stored_schema
    partition_cols = [k for k in info.partition_keys]
    return Schema.from_arrow_schema(info.arrow_schema, name="inferred",
                                    partition_columns=partition_cols)


def declared_geometries(info: "DatasetInfo") -> Dict[str, List[tuple]]:
    """Per-field distinct image shapes from the dataset's KV metadata, or {}.

    Stamped by ``write_dataset``/``stamp_dataset_metadata`` for
    variable-shape ``CompressedImageCodec`` fields; consumed by the jax
    loader's ``decode_placement='device-mixed'`` path, which checks that its
    largest canvas holds every declared geometry (and surfaced in loader
    diagnostics)."""
    raw = info.kv_metadata.get(GEOMETRIES_METADATA_KEY)
    if not raw:
        return {}
    try:
        parsed = json.loads(raw)
    except (ValueError, TypeError):
        logger.warning("unparseable %s metadata ignored", GEOMETRIES_METADATA_KEY)
        return {}
    return {name: [tuple(int(d) for d in shape) for shape in shapes]
            for name, shapes in parsed.items()}


def write_metadata_file(fs: pafs.FileSystem, root: str, arrow_schema: pa.Schema,
                        kv_metadata: Dict[bytes, bytes]) -> None:
    """Write ``_common_metadata`` with merged KV (reference utils.py:90-134)."""
    existing = _read_kv_metadata(fs, root)
    merged = {**existing, **kv_metadata}
    schema = arrow_schema.with_metadata(merged)
    pq.write_metadata(schema, posixpath.join(root, "_common_metadata"), filesystem=fs)


def collect_row_group_counts(fs: pafs.FileSystem, root: str,
                             files: List[str]) -> Dict[str, List[int]]:
    """Per-file rowgroup row counts keyed by path relative to ``root``."""
    with ThreadPoolExecutor(max_workers=_FOOTER_READ_THREADS) as pool:
        results = list(pool.map(lambda p: _footer_row_groups(fs, p), sorted(files)))
    return {posixpath.relpath(f, root): counts
            for f, counts in zip(sorted(files), results)}
