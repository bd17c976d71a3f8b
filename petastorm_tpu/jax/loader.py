"""JaxDataLoader: columnar batches -> device-sharded jax.Array pytrees.

Reference parity: petastorm/pytorch.py DataLoader/BatchedDataLoader (shuffling
buffer -> collate -> torch tensors, pytorch.py:130-367) and tf_utils
``make_petastorm_dataset`` (tf_utils.py:329-399).  What replaces what:

* torch shuffling buffers        -> columnar numpy RandomShufflingBuffer
                                    (petastorm_tpu/shuffle.py)
* default_collate per batch      -> exact-size batch assembly crossing rowgroup
                                    boundaries (reference's un-wired
                                    batching_table_queue, SURVEY.md 2.13)
* torch.as_tensor(device=...)    -> ``jax.make_array_from_process_local_data``
                                    with an explicit NamedSharding: each host
                                    feeds exactly its slice of the global batch;
                                    XLA moves shards over ICI/DCN
* tf py_func/queue runners       -> a two-stage producer (assembly thread ->
                                    bounded host queue -> transfer thread ->
                                    bounded device queue, each depth
                                    ``prefetch``): the blocking host->device
                                    copy overlaps the next batch's numpy
                                    assembly, and both overlap the device step

TPU-specific behavior:

* dtype promotion happens here, once, at the device boundary
  (petastorm_tpu/dtypes.jax_feed_dtype - uint16->int32 etc., f64->f32).
* variable-shape fields must be resolved to static shapes via ``pad_shapes``
  (XLA compiles per shape; pad-to-bucket beats recompilation) or excluded.
* string/object fields cannot reach the device: select them out with ``fields=``
  or keep them host-side via ``host_fields``.
* sequence-parallel consumers: pass a PartitionSpec sharding the sequence axis
  (e.g. P('data', 'seq')); the loader materializes only this host's sequence
  slice before assembly (petastorm_tpu/parallel/mesh.local_data_slice).
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from petastorm_tpu.batch import ColumnBatch
from petastorm_tpu.dtypes import jax_feed_dtype
from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.native.image import COEF_COLUMN_SEP as _COEF_SEP
from petastorm_tpu.native.image import MIXED_CELL_SUFFIX as _MIXED_SUFFIX
from petastorm_tpu.parallel.mesh import local_data_slice
from petastorm_tpu.shuffle import (NoopShufflingBuffer, RandomShufflingBuffer,
                                   iter_batched, iter_batched_multi)
from petastorm_tpu.telemetry import NULL_CONTEXT as _NULL_CONTEXT
from petastorm_tpu.telemetry import resolve as _resolve_telemetry

logger = logging.getLogger(__name__)

_QUEUE_POLL_S = 0.1
#: default straggler-release threshold (straggler_release_s='auto' with a
#: decorrelation floor): long enough that a healthy pipeline never trips it,
#: short enough that one hung/slow rowgroup does not idle the device
_DEFAULT_STRAGGLER_RELEASE_S = 2.0
#: 'auto' transfer-commit probe: a readiness sync costing more than this per
#: trivial op marks a runtime where a sync is expensive - async chaining then
#: pipelines strictly better
_COMMIT_PROBE_THRESHOLD_S = 0.02
#: key suffix of the per-image (height, width) that rides a batch beside
#: each 'device-mixed' field (``'image'`` -> ``'image_extent'``)
EXTENT_SUFFIX = "_extent"
#: images per on-chip finish call of a 'device-mixed' field: a canvas's
#: images go in groups of the largest size, the rest in the smallest size
#: that holds them (sizes above the local batch are cut to it)
_MIXED_GROUP_SIZES = (8, 16, 32)


class _Done:
    pass


def _mixed_key(name: str) -> str:
    """The wire column of a 'device-mixed' field (object cells from the
    workers, then a ``_MixedPack`` from the assembly thread)."""
    return f"{name}{_COEF_SEP}{_MIXED_SUFFIX}"


def _canvas_plane_shapes(canvas: Tuple[int, int],
                         sampling: Tuple[Tuple[int, int], ...]):
    """Per component, the (blocks_h, blocks_w) of a canvas rounded up to
    whole MCUs: it then holds the blocks of any image no larger than it."""
    max_h = max(h for h, _ in sampling)
    max_v = max(v for _, v in sampling)
    height = -(-canvas[0] // (8 * max_v)) * max_v
    width = -(-canvas[1] // (8 * max_h)) * max_h
    return [(height * v // max_v, width * h // max_h) for h, v in sampling]


class _CanvasGroup:
    """Host arrays of one on-chip finish call: ``len(rows)`` images (rows
    of -1 are padding) in one canvas and chroma layout."""

    __slots__ = ("canvas", "sampling", "planes", "qtabs", "extents", "rows")

    def __init__(self, canvas, sampling, planes, qtabs, extents, rows):
        self.canvas, self.sampling = canvas, sampling
        self.planes, self.qtabs = planes, qtabs
        self.extents, self.rows = extents, rows

    @classmethod
    def pack(cls, col, members, extents, canvas, sampling, size):
        planes = tuple(np.zeros((size, bh, bw, 64), np.int16)
                       for bh, bw in _canvas_plane_shapes(canvas, sampling))
        qtabs = np.ones((size, len(planes), 64), np.uint16)
        ext = np.zeros((size, 2), np.int32)
        rows = np.full(size, -1, np.int32)
        for k, i in enumerate(members):
            cell_planes, qtab, _ = col[i]
            for dst, src in zip(planes, cell_planes):
                dst[k, :src.shape[0], :src.shape[1]] = src
            qtabs[k] = qtab
            ext[k] = extents[i]
            rows[k] = i
        return cls(canvas, sampling, planes, qtabs, ext, rows)


class _MixedPack:
    """A batch's 'device-mixed' column after the 'mixed-pack' stage: each
    row's (height, width) and the canvas groups that finish them."""

    __slots__ = ("extents", "groups")

    def __init__(self, extents: np.ndarray, groups: List[_CanvasGroup]):
        self.extents, self.groups = extents, groups

    def __len__(self) -> int:
        return len(self.extents)


class _Error:
    def __init__(self, exc: BaseException):
        self.exc = exc


class _TimedSource:
    """Runs a prepared-batch generator on its own thread so the assembly
    pump can poll it WITH A TIMEOUT (straggler release needs to notice "no
    raw batch for T seconds" while the reader call is still blocked).

    ``get(timeout)`` returns the next batch, raises ``queue.Empty`` on
    timeout, ``StopIteration`` at end of stream, or re-raises the
    generator's failure.  The thread honors the loader's stop event on both
    ends of its bounded queue.
    """

    _DONE = object()

    def __init__(self, gen, stop_event: threading.Event):
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._stop = stop_event
        self._thread = threading.Thread(target=self._run, args=(gen,),
                                        daemon=True,
                                        name="petastorm-tpu-jax-fetch")
        self._thread.start()

    def _run(self, gen) -> None:
        try:
            for item in gen:
                if self._stop.is_set():
                    return
                self._put(item)
            self._put(self._DONE)
        except BaseException as exc:  # noqa: BLE001 - forwarded to the pump
            self._put(_Error(exc))

    def _put(self, value) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(value, timeout=_QUEUE_POLL_S)
                return
            except queue.Full:
                continue

    def get(self, timeout: Optional[float]):
        while True:
            try:
                value = self._q.get(
                    timeout=timeout if timeout is not None else _QUEUE_POLL_S)
            except queue.Empty:
                if self._stop.is_set():
                    raise StopIteration from None
                if timeout is not None:
                    raise
                continue
            if value is self._DONE:
                raise StopIteration
            if isinstance(value, _Error):
                raise value.exc
            return value

    def join(self, timeout: float = 2.0) -> None:
        self._thread.join(timeout=timeout)


class JaxDataLoader:
    """Iterate device-sharded batches (dict field -> jax.Array) from a Reader.

    ``batch_size`` is the GLOBAL batch size across the whole mesh; this process
    materializes only its slice (global/process_count for a data-sharded axis).

    With ``drop_last=False`` on a mesh, the final partial batch is zero-padded to
    the static batch size (constant shapes = no XLA recompile, even shards) and
    carries an extra ``'_valid_rows'`` host int with the true row count.

    ``valid_mask_field='mask'`` (mesh only) adds a synthetic 1-D float32 device
    column: 1.0 for real rows, 0.0 for padding, sharded exactly like the data
    fields' batch axis.  Being a global array it is identical on every host -
    weight per-example losses by it instead of branching on the host-local
    ``'_valid_rows'`` (which differs across hosts on drained pads and would
    diverge pod control flow; see ``drain()``).

    ``stack_batches=K`` (scan-feed delivery): each delivered unit is a stack
    of K consecutive batches - device arrays of shape ``(K, batch, ...)``
    sharded ``PartitionSpec(None, *spec)`` - shipped in ONE transfer, for
    consumers running K train steps per dispatch via ``lax.scan``.  Both the
    per-unit transfer count and the per-call dispatch cost amortize K-fold.
    Semantics shift to stack granularity: ``drop_last=True`` also drops a
    final short stack; with ``drop_last=False`` missing steps and partial
    rows zero-pad, ``'_valid_rows'`` becomes a per-step ``(K,)`` int array,
    the valid mask is ``(K, batch)``, and ``drain()``/``state_dict()`` count
    whole stacks.  Incompatible with ``device_shuffle_capacity`` and
    multi-bucket ``pad_shapes`` (a 'device-mixed' field's canvases excepted:
    its delivered shape is fixed).

    A ``decode_placement='device-mixed'`` field (JPEGs of any geometry) is
    finished on the chip in fixed canvases: its ``pad_shapes`` entry (one
    ``(H, W[, C])`` or a list of them; a fixed-shape field uses its schema
    shape) names the canvases, and the largest, which must hold every
    other, is the delivered shape.  The host pads each image's coefficient
    planes top-left into the smallest canvas that holds it (the
    ``mixed-pack`` stage), and the chip finishes groups of images one
    canvas at a time.  Inside each image's extent the pixels are those of
    the image's own decode; outside it they are 0.  The batch carries each
    image's ``(height, width)`` as an int32 ``(batch, 2)`` array under
    ``'<field>_extent'``, sharded on the batch axis like the field.  An
    image larger than the largest canvas is an error.  The finish compiles
    once per (chroma layout, canvas, group size of 8, 16 or 32 images):
    all of a layout's at the batch where the layout first shows, so the
    count of programs (``loader.mixed_finish_programs``) never depends on
    how many geometries the data holds.

    ``straggler_release_s`` (MinatoLoader-style, default ``'auto'``): when
    no raw batch arrives for this long while the shuffle buffer already
    holds a full batch that only its decorrelation floor
    (``min_after_retrieve``) is withholding, the floor is bypassed and the
    batch emitted - one slow-decoding rowgroup stops gating batch assembly,
    and its rows ride a later batch.  ``'auto'`` = 2 s whenever a floor
    exists; ``None`` disables.  Counted in ``loader.straggler_releases``
    telemetry and ``diagnostics['straggler_releases']``.

    ``transfer_commit`` (default ``'auto'``): whether the transfer thread
    blocks until each batch's host->device copies land.  A commit waits on
    what was copied from the host - the fields, the valid mask and, for
    fields decoded on the chip, their coefficient planes - never on the
    on-chip JPEG finish computed from them, which the device's stream order
    runs before the step that reads it; so a fault inside the finish
    surfaces where the consumer uses the array, not in the transfer thread.
    ``'auto'`` probes the runtime's readiness-sync cost once and starts in
    ASYNC-CHAINED mode (no per-batch commit) on runtimes where a sync is
    expensive; ``True``/``False`` pin it.  The adaptive mid-run disable
    stays armed as the backstop in 'auto' and True modes.

    Readers with ``decode_placement={'field': 'auto'}`` (the live
    host<->device decode split) are handled transparently: pixel-form and
    coefficient-form rowgroups assemble in separate buffers, so a split
    flip never mixes wire forms within one delivered batch.  Incompatible
    with ``stack_batches > 1``.
    """

    def __init__(self,
                 reader,
                 batch_size: int,
                 mesh: Optional[Mesh] = None,
                 shardings: Union[None, PartitionSpec, Dict[str, PartitionSpec]] = None,
                 fields: Optional[Sequence[str]] = None,
                 host_fields: Sequence[str] = (),
                 shuffling_queue_capacity: int = 0,
                 min_after_retrieve: Optional[int] = None,
                 buffer_seed: Optional[int] = None,
                 pad_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
                 pad_values: Union[float, Dict[str, float]] = 0,
                 drop_last: bool = True,
                 prefetch: Optional[int] = None,
                 keep_wide_dtypes: bool = False,
                 transform_fn: Optional[Callable[[Dict[str, np.ndarray]],
                                                 Dict[str, np.ndarray]]] = None,
                 trace_dir: Optional[str] = None,
                 device_shuffle_capacity: int = 0,
                 device_shuffle_seed: Optional[int] = None,
                 valid_mask_field: Optional[str] = None,
                 stack_batches: int = 1,
                 straggler_release_s: Union[None, float, str] = "auto",
                 transfer_commit: Union[bool, str] = "auto",
                 telemetry=None):
        self._reader = reader
        #: pipeline telemetry (petastorm_tpu.telemetry): defaults to the
        #: reader's recorder so one object observes reader -> pool -> loader;
        #: no-op unless enabled.  Loader stages: 'host-assemble' (per raw
        #: reader batch: field selection, pad-to-bucket), 'host-prep' (per
        #: delivered batch: transform_fn, row padding, mask) and
        #: 'device-transfer' (make_array / device_put / commit).
        self._telemetry = _resolve_telemetry(
            telemetry if telemetry is not None
            else getattr(reader, "telemetry", None))
        self._m_consumer_wait = self._telemetry.counter(
            "loader.consumer_wait_s")
        self._m_delivered = self._telemetry.counter("loader.batches_delivered")
        #: commits that left an on-chip computed field (the JPEG finish) to
        #: the device's stream order (see _commit)
        self._m_transfer_only = self._telemetry.counter(
            "loader.commits_transfer_only")
        #: 'device-mixed' fields: pixels of the images' own extents and of
        #: the canvases they were packed into, and the finish programs
        #: compiled (fixed per chroma layout, see _finish_program)
        self._m_mixed_valid = self._telemetry.counter(
            "loader.mixed_pixels_valid")
        self._m_mixed_canvas = self._telemetry.counter(
            "loader.mixed_pixels_canvas")
        self._m_mixed_programs = self._telemetry.counter(
            "loader.mixed_finish_programs")
        #: host-queue depth gauge: with the prefetch-depth gauge (set in
        #: __next__) the metrics sampler sees both producer stages' backlogs
        self._g_host_depth = self._telemetry.gauge("loader.host_queue_depth")
        self._g_prefetch_depth = self._telemetry.gauge("loader.prefetch_depth")
        if self._telemetry.enabled:
            register = getattr(self._telemetry, "register_stage", None)
            if register is not None:
                # visible as "no samples yet" before the first batch lands;
                # 'commit' stays at zero while commits are off
                for stage in ("host-assemble", "host-prep",
                              "device-transfer", "commit"):
                    register(stage)
        self._mesh = mesh
        self._specs = shardings
        #: K > 1 = scan-feed delivery: each delivered unit stacks K
        #: consecutive batches as (K, batch, ...) device arrays shipped in ONE
        #: transfer, for consumers that run K train steps per dispatch via
        #: ``lax.scan`` (amortizes the fixed per-call dispatch cost AND the
        #: per-transfer dispatch, which the hand-stacked ``jnp.stack``
        #: pattern still paid K times).
        #: Reference analog: none - the TPU-native replacement for feeding
        #: BatchedDataLoader one batch per step (petastorm/pytorch.py:257-367)
        if stack_batches < 1:
            raise PetastormTpuError("stack_batches must be >= 1")
        self._stack = int(stack_batches)
        # each entry: one target tuple, or a LIST of bucket tuples - the
        # smallest bucket fitting the batch is chosen per batch, bounding XLA
        # recompiles to the bucket count (SURVEY.md section 7 hard part (d))
        self._pad_shapes = {name: _normalize_buckets(name, spec)
                            for name, spec in (pad_shapes or {}).items()}
        self._pad_values = pad_values
        self._drop_last = drop_last
        self._keep_wide = keep_wide_dtypes
        self._transform_fn = transform_fn
        self._host_fields = list(host_fields)
        #: fields arriving as raw jpeg bytes (reader decode_placement='device');
        #: decoded on-chip in _emit via ops/jpeg.decode_coefficients
        self._device_decode = list(getattr(reader, "device_decode_fields", ()) or ())
        #: subset using the mixed-geometry wire format ('device-mixed'):
        #: packed into fixed canvases, finished per canvas group
        self._mixed_decode = frozenset(
            getattr(reader, "device_decode_mixed", ()) or ())
        #: subset under the LIVE host<->device decode split
        #: (decode_placement='auto'): a raw batch carries EITHER the pixel
        #: column or coefficient planes; assembly keeps the two forms in
        #: separate buffers (iter_batched_multi) so a split flip never mixes
        #: column sets within one delivered batch
        self._split_decode = frozenset(
            getattr(reader, "device_decode_split", ()) or ())
        #: MinatoLoader-style straggler release: when no raw batch arrives
        #: for this long while a full batch sits behind the shuffle buffer's
        #: decorrelation floor, the floor is bypassed and the batch emitted
        #: (the slow rowgroup's rows ride a later batch).  'auto' = 2 s when
        #: a floor exists, else off; None disables.
        if straggler_release_s == "auto":
            self._straggler_s: Optional[float] = (
                _DEFAULT_STRAGGLER_RELEASE_S
                if shuffling_queue_capacity and (
                    min_after_retrieve is None or min_after_retrieve > 0)
                else None)
        else:
            self._straggler_s = (float(straggler_release_s)
                                 if straggler_release_s else None)
        if (self._straggler_s is not None
                and getattr(reader, "deterministic", "off") == "seed"):
            # seed-stable delivery (docs/operations.md "Reproducibility"):
            # a straggler release fires on wall-clock timing, so one near an
            # epoch edge moves rows across a batch boundary between runs -
            # the exact nondeterminism deterministic='seed' exists to
            # eliminate.  The reader's reorder stage already prevents the
            # slow-rowgroup head-of-line blocking the release worked around.
            logger.warning(
                "straggler_release_s is a timing-driven floor bypass and is"
                " disabled under deterministic='seed' delivery (it would"
                " move rows across batch boundaries between runs); pass"
                " deterministic='off' to the reader if straggler release"
                " matters more than bit-identical batches")
            self._straggler_s = None
        self._m_straggler = self._telemetry.counter(
            "loader.straggler_releases")
        #: transfer-commit policy (see _commit): 'auto' probes the runtime's
        #: readiness-sync cost once and starts with async-chained transfers
        #: (no per-batch commit) on runtimes where a sync is expensive;
        #: True/False pin it (True keeps the adaptive breach backstop)
        # identity, not equality: `0 in (True, False, 'auto')` is True via
        # 0 == False, but the `is False` check below would then keep commits
        # ON for transfer_commit=0 - the opposite of what was asked
        if not any(transfer_commit is v for v in (True, False, "auto")):
            raise PetastormTpuError(
                f"transfer_commit must be True, False or 'auto';"
                f" got {transfer_commit!r}")
        self._commit_mode = transfer_commit
        #: per mixed field, layout meta bytes -> JpegCoefLayout of each
        #: geometry seen (parsed once; counted in diagnostics)
        self._mixed_geometries: Dict[str, Dict[bytes, object]] = {}
        #: (field, sampling, canvas, group size) -> compiled on-chip finish
        self._finish_programs: Dict[Tuple, object] = {}
        #: (field, h, w) geometries already warned about as missing from the
        #: dataset-level declared-geometry contract (one warning each)
        self._geom_warned: set = set()
        #: the contract is immutable for an open reader: parse the KV JSON
        #: once here, not per decoded geometry group on the hot path
        self._declared_geometries: Dict = (
            getattr(reader, "declared_geometries", None) or {})

        # output_schema describes the columns iter_batches actually yields
        # (differs from reader.schema for ngram readers)
        schema = getattr(reader, "output_schema", None) or reader.schema
        self._schema = schema
        self._fields = list(fields) if fields is not None else [
            f.name for f in schema if f.name not in self._host_fields]
        unknown = [f for f in self._fields + self._host_fields if f not in schema]
        if unknown:
            raise PetastormTpuError(f"Unknown fields {unknown}; schema has"
                                    f" {[f.name for f in schema]}")
        host_device = [f for f in self._host_fields if f in self._device_decode]
        if host_device:
            raise PetastormTpuError(
                f"fields {host_device} use decode_placement='device' (the"
                " worker ships coefficient planes, not pixels) and cannot be"
                " delivered host-side; use decode_placement='host' or drop"
                " them from host_fields")
        if not self._fields:
            raise PetastormTpuError(
                "JaxDataLoader needs at least one device-deliverable field"
                " (all schema fields were excluded or routed to host_fields)")
        #: delivered 'device-mixed' field -> the key of its per-image extents
        self._extent_keys = {name: name + EXTENT_SUFFIX for name in self._fields
                             if name in self._mixed_decode}
        self._extent_field = {k: f for f, k in self._extent_keys.items()}
        clash = [k for k in self._extent_keys.values() if k in schema]
        if clash:
            raise PetastormTpuError(
                f"'device-mixed' fields deliver their image extents under"
                f" {clash}, which the schema already names; rename the field")
        if self._extent_keys and self._telemetry.enabled:
            register = getattr(self._telemetry, "register_stage", None)
            if register is not None:
                register("mixed-pack")

        #: synthetic per-row validity column (1.0 = real row, 0.0 = padding).
        #: Unlike the host-local '_valid_rows' int, the mask is a GLOBAL device
        #: array assembled like any data field, so every host of a pod holds
        #: the same logical values - the only safe signal to weight losses by
        #: under collectives, where branching on host-local '_valid_rows'
        #: diverges control flow across hosts and hangs the pod (see drain())
        self._valid_mask = valid_mask_field
        if valid_mask_field is not None:
            if mesh is None:
                raise PetastormTpuError(
                    "valid_mask_field only applies to mesh delivery: without a"
                    " mesh no zero-padding happens, every delivered row is real")
            if valid_mask_field in schema:
                raise PetastormTpuError(
                    f"valid_mask_field {valid_mask_field!r} collides with a"
                    " schema field; pick an unused name")
            if valid_mask_field == "_valid_rows":
                raise PetastormTpuError(
                    "valid_mask_field cannot be '_valid_rows': that key is"
                    " reserved for the host-local valid-row count")
        self._validate_deliverable(schema)

        if batch_size < 1:
            raise PetastormTpuError("batch_size must be >= 1")
        self._global_batch = batch_size
        self._local_rows = self._local_layout()
        if self._mesh is not None:
            for name in self._fields:
                if name in self._mixed_decode:
                    self._validate_mixed_scatter_layout(name)

        #: HBM-resident exchange shuffle over whole device batches (the TPU
        #: analog of the reference's GPU-tensor BatchedDataLoader buffers,
        #: petastorm/pytorch_shuffling_buffer.py) - composes with the host
        #: shuffling buffer below, which mixes rows before batch assembly
        if self._stack > 1 and self._split_decode:
            raise PetastormTpuError(
                f"stack_batches={self._stack} cannot be combined with the"
                f" live decode split (decode_placement='auto' fields"
                f" {sorted(self._split_decode)}): the K stacked batches could"
                " straddle a split flip and mix wire forms. Pin the split"
                " with decode_placement='host'/'device' for scan-feed"
                " delivery.")
        if self._stack > 1:
            bucketed = [n for n, b in self._pad_shapes.items()
                        if len(b) > 1 and n not in self._mixed_decode]
            if bucketed:
                raise PetastormTpuError(
                    f"stack_batches={self._stack} needs one static shape per"
                    f" field, but {bucketed} use multi-bucket pad_shapes (the"
                    " bucket choice could differ between the K stacked"
                    " batches); give them a single pad target instead.")
            if device_shuffle_capacity:
                raise PetastormTpuError(
                    "stack_batches cannot be combined with"
                    " device_shuffle_capacity: the HBM exchange buffer holds"
                    " single batches. Use the host shuffling buffer"
                    " (shuffling_queue_capacity) instead.")

        # under deterministic='seed' delivery, unseeded buffer RNGs derive
        # from the reader's seed root (explicit seeds win): with
        # in-plan-order arrival from the reorder stage, every
        # shuffle-buffer draw is then a pure function of (seed, retrieval
        # position) and batch composition is bit-identical across runs
        from petastorm_tpu.seeding import reader_buffer_seed

        buffer_seed = reader_buffer_seed(reader, "loader.shuffle_buffer",
                                         buffer_seed)
        if device_shuffle_capacity:
            device_shuffle_seed = reader_buffer_seed(
                reader, "loader.device_shuffle", device_shuffle_seed)
        self._device_buffer = None
        if device_shuffle_capacity:
            if self._host_fields:
                raise PetastormTpuError(
                    "device_shuffle_capacity cannot be combined with"
                    " host_fields: host-side values cannot live in the HBM"
                    " buffer. Use the host shuffling buffer"
                    " (shuffling_queue_capacity) instead.")
            bucketed = [n for n, b in self._pad_shapes.items()
                        if len(b) > 1 and n not in self._mixed_decode]
            if bucketed:
                raise PetastormTpuError(
                    f"device_shuffle_capacity needs uniform batch shapes, but"
                    f" {bucketed} use multi-bucket pad_shapes; give them a"
                    " single pad target instead.")
            from petastorm_tpu.jax.device_buffer import DeviceShufflingBuffer

            self._device_buffer = DeviceShufflingBuffer(
                device_shuffle_capacity, seed=device_shuffle_seed)
        #: partial batches held back so they are emitted after the drain
        self._tail_batches = []

        if shuffling_queue_capacity and shuffling_queue_capacity > 0:
            min_after = (min_after_retrieve if min_after_retrieve is not None
                         else shuffling_queue_capacity // 2)
            self._make_buffer = lambda: RandomShufflingBuffer(
                shuffling_queue_capacity, min_after, seed=buffer_seed)
        else:
            self._make_buffer = NoopShufflingBuffer

        if prefetch is None:
            # None = planner-seeded: a reader that ran the static pipeline
            # planner (petastorm_tpu.planner) carries a planned prefetch
            # depth with provenance; everything else keeps the historical
            # default of 2.  An explicit int pins the depth.
            prefetch = 2
            verdict = getattr(reader, "planner", None)
            planned = getattr(verdict, "knobs", {}).get("prefetch") \
                if verdict is not None else None
            if planned is not None and planned.source in ("profile",
                                                          "metadata"):
                prefetch = int(planned.value)
        self._out: "queue.Queue" = queue.Queue(maxsize=max(prefetch, 1))
        # two-stage producer: the assembly thread does the numpy work (batch
        # formation, shuffle, pad) and the transfer thread does the device
        # dispatch (make_array/device_put BLOCKS for the host->device copy,
        # several ms of IO per batch) - so transfers overlap the next batch's
        # host prep instead of serializing with it
        self._host_q: "queue.Queue" = queue.Queue(maxsize=max(prefetch, 1))
        self._stop_event = threading.Event()
        self._thread = threading.Thread(target=self._assemble, daemon=True,
                                        name="petastorm-tpu-jax-assembly")
        self._transfer_thread = threading.Thread(
            target=self._transfer, daemon=True,
            name="petastorm-tpu-jax-transfer")
        self._started = False
        self._finished = False
        self._failure: Optional[BaseException] = None
        self._delivered_batches = 0
        #: sequence number of the next batch the transfer thread emits: the
        #: ``batch`` id its spans carry (the delivered batch's number, unless
        #: device_shuffle_capacity reorders delivery)
        self._emit_seq = 0
        #: producer threads that failed to quiesce within the stop() join
        #: budget ([{thread, stage}]); surfaced in diagnostics so a silent
        #: shutdown wedge is visible post-mortem, not swallowed
        self._unquiesced: list = []
        #: cumulative seconds the consumer spent blocked waiting for a batch
        #: (the live device-idle signal; see also the throughput CLI's
        #: --simulated-step-ms for an offline measurement)
        self._consumer_wait_s = 0.0
        #: batches emitted past the shuffle decorrelation floor because the
        #: source straggled (see straggler_release_s)
        self._straggler_releases = 0
        #: when set, a jax.profiler trace (device + host ingest activity,
        #: viewable in TensorBoard/Perfetto) brackets the loader's lifetime
        self._trace_dir = trace_dir
        self._tracing = False
        #: producer has queued its _Done/_Error end-of-stream marker
        self._sentinel_pending = False
        #: adaptive transfer commit (see _commit): flips False permanently
        #: when the runtime's readiness sync is pathologically expensive;
        #: transfer_commit='auto' additionally probes the sync cost up front
        #: (async-chained transfer is then the DEFAULT on round-trip
        #: runtimes, not a mid-run discovery), False starts disabled
        self._commit_transfers = self._commit_mode is not False
        self._commit_probed = self._commit_mode != "auto"
        self._commit_probe_ms: Optional[float] = None
        self._commit_count = 0       # commits observed (first is warmup)
        self._commit_breaches = 0    # CONSECUTIVE over-threshold commits
        #: per-(field, trailing-shape) cache of (sharding, local slice) - static
        #: for the loader's lifetime, rebuilt per batch otherwise
        self._placement_cache: Dict[Tuple[str, Tuple[int, ...]],
                                    Tuple[NamedSharding, Tuple[slice, ...]]] = {}
        #: (trailing shape, dtype) each field was LAST emitted with
        #: (post-transform_fn, post-promotion, post-bucket-pad) - drain
        #: alignment pads must match the last emitted batch (the same
        #: semantics as the template path, which pads from the last drained
        #: batch), not the schema
        self._emitted_layout: Dict[str, Tuple[Tuple[int, ...], np.dtype]] = {}

        # closed-loop autotuning (petastorm_tpu.autotune): an autotuned
        # reader's controller gains this loader's prefetch depth as a knob
        controller = getattr(reader, "autotune", None)
        if controller is not None and hasattr(controller, "attach_loader"):
            controller.attach_loader(self)

    # -- runtime-adjustable prefetch (docs/operations.md "Autotuning") --------

    @property
    def prefetch(self) -> int:
        """Current per-stage producer queue bound (both the host-assembly
        and the device-transfer queues; runtime-adjustable via
        :meth:`set_prefetch`)."""
        return self._out.maxsize

    def set_prefetch(self, depth: int) -> int:
        """Resize both producer-stage queue bounds in place.

        Widening wakes any producer blocked on a full queue immediately;
        narrowing never drops queued batches - puts simply block until the
        consumer drains below the new bound.  This is the autotune
        controller's prefetch knob, and is safe to call directly while the
        loader runs.  Returns the new depth.
        """
        depth = max(1, int(depth))
        for q in (self._host_q, self._out):
            # stdlib queue.Queue: maxsize is only read under the mutex, and
            # not_full shares that mutex - mutate and wake waiters atomically
            with q.not_full:
                q.maxsize = depth
                q.not_full.notify_all()
        return depth

    # -- shape/sharding bookkeeping ------------------------------------------

    def _mixed_canvases(self, name: str) -> List[Tuple[int, int]]:
        """(H, W) canvases of a 'device-mixed' field, smallest area first:
        the schema shape when fixed, else its ``pad_shapes`` entry (one
        target or a list).  The last holds every other and is the delivered
        (H, W)."""
        field = self._schema[name]
        if field.is_fixed_shape:
            return [tuple(field.shape[:2])]
        buckets = self._pad_shapes.get(name)
        if not buckets:
            raise PetastormTpuError(
                f"decode_placement='device-mixed' field {name!r} has variable"
                f" shape {field.shape}: give it pad_shapes canvases (one"
                " (H, W[, C]) or a list of them) that every image fits into")
        for b in buckets:
            if len(b) != len(field.shape):
                raise PetastormTpuError(
                    f"pad_shapes[{name!r}] canvas {tuple(b)} rank differs"
                    f" from the field shape {field.shape}")
        if len({tuple(b[2:]) for b in buckets}) > 1:
            raise PetastormTpuError(
                f"pad_shapes[{name!r}] canvases {buckets} disagree on the"
                " channel count")
        canvases = sorted({(int(b[0]), int(b[1])) for b in buckets},
                          key=lambda c: (c[0] * c[1], c))
        largest = (max(c[0] for c in canvases), max(c[1] for c in canvases))
        if canvases[-1] != largest:
            raise PetastormTpuError(
                f"pad_shapes[{name!r}] canvases {canvases}: the largest must"
                f" hold every other (add {largest}); it is the delivered shape")
        return canvases

    def _mixed_target(self, name: str) -> Tuple[int, ...]:
        """Static (H, W[, C]) a 'device-mixed' field is delivered at: its
        largest canvas."""
        field = self._schema[name]
        tail = (tuple(field.shape[2:]) if field.is_fixed_shape
                else tuple(self._pad_shapes[name][0][2:]))
        return self._mixed_canvases(name)[-1] + tail

    def _validate_deliverable(self, schema) -> None:
        for name in self._fields:
            if name in self._mixed_decode:
                self._check_canvases_hold_declared(name)
                if self._mesh is not None:
                    # mesh delivery works because the decode stays HOST-LOCAL
                    # (each host compiles only the geometries it encounters -
                    # bucket sets may differ per host freely) and only the
                    # decoded pixels are declared a global array afterwards
                    # (_scatter_local_rows) - so only the batch axis may shard
                    spec = self._spec_for(name)
                    if any(ax is not None for ax in spec[1:]):
                        raise PetastormTpuError(
                            f"decode_placement='device-mixed' field {name!r}:"
                            " only the batch axis may be sharded (the decode"
                            " is host-local; trailing image axes cannot span"
                            f" hosts). Got spec {spec}.")
                continue
            if name in self._device_decode:
                continue  # raw jpeg bytes in, schema-shaped uint8 out (on-chip)
            field = schema[name]
            if field.dtype.kind in ("U", "S", "O", "M", "m"):
                raise PetastormTpuError(
                    f"Field {name!r} (dtype {field.dtype}) cannot be fed to a"
                    " device. Exclude it with fields=, or keep it host-side via"
                    " host_fields=.")
            if not field.is_fixed_shape and name not in self._pad_shapes:
                raise PetastormTpuError(
                    f"Field {name!r} has variable shape {field.shape}; XLA needs"
                    " static shapes - give it a pad_shapes entry (pad-to-bucket)"
                    " or exclude it.")

    def _spec_for(self, name: str) -> PartitionSpec:
        field = self._extent_field.get(name)
        if field is not None:
            # a mixed field's extents shard their batch axis like the field
            base = self._spec_for(field)
            return PartitionSpec(base[0] if len(base) else None)
        if isinstance(self._specs, dict):
            spec = self._specs.get(name)
        else:
            spec = self._specs
        if name == self._valid_mask and (
                not isinstance(self._specs, dict) or name not in self._specs):
            # the 1-D mask must shard its only axis exactly like the data
            # fields shard their batch axis, or local row counts diverge
            base = self._spec_for(self._fields[0]) if self._fields else None
            return PartitionSpec(
                base[0] if base is not None and len(base) else None)
        if spec is None:
            axis = self._mesh.axis_names[0] if self._mesh is not None else "data"
            spec = PartitionSpec(axis)
        return spec

    def _validate_mixed_scatter_layout(self, name: str) -> None:
        """Construction-time contract for 'device-mixed' mesh delivery: this
        host's addressable batch-axis shards must tile one contiguous block
        of exactly ``_local_rows`` rows (``_scatter_local_rows`` slices one
        host-local np.ndarray).  Depends only on (mesh, spec, global batch) -
        dim-0 slices of a batch-axis NamedSharding are independent of the
        trailing image dims - so a misconfigured mesh/spec fails fast here,
        not with an opaque shape error from
        ``make_array_from_single_device_arrays`` after the first decode."""
        spec = self._spec_for(name)
        batch_axis = spec[0] if len(spec) else None
        if batch_axis is None and self._local_rows < self._global_batch:
            # replicated batch is fine single-host (the host holds the full
            # batch); across processes each host holds only its local rows,
            # so a 'replicated' array would silently diverge per host
            raise PetastormTpuError(
                f"field {name!r}: decode_placement='device-mixed' requires the"
                " batch axis to be sharded when the batch spans processes"
                " (PartitionSpec leading entry is None, but this host"
                f" materializes only {self._local_rows} of the"
                f" {self._global_batch}-row global batch)."
                f" mesh={self._mesh!r} spec={spec!r}")
        batch_sharding = NamedSharding(self._mesh, PartitionSpec(batch_axis))
        global_shape = (self._global_batch,)
        idx_map = batch_sharding.addressable_devices_indices_map(global_shape)
        spans = sorted(
            ((sl[0].start or 0,
              sl[0].stop if sl[0].stop is not None else global_shape[0])
             for sl in idx_map.values()))
        lo = spans[0][0]
        covered = lo
        for a, b in spans:
            if a > covered:   # gap: another process' rows sit between ours
                raise PetastormTpuError(
                    f"field {name!r}: this host's addressable batch-axis"
                    f" shards are not contiguous (gap at rows [{covered},"
                    f" {a}) inside local span [{lo}, {spans[-1][1]}))."
                    " decode_placement='device-mixed' requires a mesh whose"
                    " device order keeps each process' batch rows contiguous;"
                    f" mesh={self._mesh!r} spec={spec!r}")
            covered = max(covered, b)
        if covered - lo != self._local_rows:
            raise PetastormTpuError(
                f"field {name!r}: addressable batch shards cover"
                f" {covered - lo} rows but this host owns {self._local_rows};"
                f" mesh={self._mesh!r} spec={spec!r} is not a plain"
                " batch-sharded layout supported by"
                " decode_placement='device-mixed'")

    def _local_layout(self) -> int:
        """Rows of the global batch this process materializes."""
        if self._mesh is None:
            return self._global_batch
        local_rows = None
        for name in self._fields:
            spec = self._spec_for(name)
            # probe only the batch axis: trailing sharded dims resolve per batch
            batch_axis_spec = PartitionSpec(spec[0] if len(spec) else None)
            sharding = NamedSharding(self._mesh, batch_axis_spec)
            sl = local_data_slice(sharding, (self._global_batch,))
            rows = sl[0].stop - sl[0].start
            if local_rows is None:
                local_rows = rows
            elif local_rows != rows:
                raise PetastormTpuError(
                    "All delivered fields must shard the batch axis identically"
                    f" (field {name!r} wants {rows} local rows, others"
                    f" {local_rows})")
        return int(local_rows)

    # -- producer thread ------------------------------------------------------

    def _prepare(self, batch: ColumnBatch) -> ColumnBatch:
        cols: Dict[str, np.ndarray] = {}
        for name in self._fields + self._host_fields:
            if name in self._device_decode:
                if name in self._split_decode and name in batch.columns:
                    # live split, HOST form: the worker shipped decoded
                    # pixels under the plain name - deliver like any field
                    cols[name] = batch.columns[name]
                    continue
                # the worker shipped the field as derived coefficient-plane
                # columns ('<name>#...'); pass them through batch assembly
                for key, col in batch.columns.items():
                    if key.startswith(name + _COEF_SEP):
                        cols[key] = col
                continue
            col = batch.columns[name]
            if name in self._pad_shapes:
                target = _pick_bucket(col, self._pad_shapes[name])
                col = _pad_to(col, target, self._pad_value_for(name),
                              self._schema[name].dtype)
            cols[name] = col
        return ColumnBatch(cols, batch.num_rows)

    def _pad_value_for(self, name: str):
        if isinstance(self._pad_values, dict):
            return self._pad_values.get(name, 0)
        return self._pad_values

    def _form_route(self, batch: ColumnBatch) -> tuple:
        """Assembly-partition key: which live-split fields arrived in HOST
        (pixel) form.  Constant () without split fields; around a split flip
        the two forms land in separate buffers and never concatenate."""
        if not self._split_decode:
            return ()
        return tuple(n for n in sorted(self._split_decode)
                     if n in batch.columns)

    def _on_straggler_release(self) -> None:
        self._straggler_releases += 1
        self._m_straggler.add(1)
        if self._straggler_releases == 1:
            # loud the first time: on a UNIFORMLY slow source (cold remote
            # reads slower than the threshold) every fetch gap releases, so
            # the decorrelation floor is effectively bypassed for the run -
            # a shuffle-quality tradeoff the operator must be able to see
            logger.warning(
                "straggler release: emitted a buffered batch past the"
                " shuffle decorrelation floor (no raw batch for %.1fs)."
                " Occasional releases are the point (a slow rowgroup must"
                " not gate assembly); FREQUENT ones mean the source is"
                " uniformly slower than straggler_release_s and the"
                " min_after_retrieve floor is being bypassed - raise the"
                " threshold or fix the source (watch"
                " loader.straggler_releases)", self._straggler_s)
        else:
            logger.debug("straggler release #%d (no raw batch for %.1fs)",
                         self._straggler_releases, self._straggler_s)

    def _assemble(self) -> None:
        """Stage 1: reader batches -> host-assembled local batches.

        Plain readers pump through :func:`iter_batched` exactly as before.
        Two features route through :func:`iter_batched_multi` instead: the
        live decode split (per-form assembly buffers) and straggler release
        (a fetch thread polls the reader with a timeout so a slow-decoding
        rowgroup stops gating emission of already-buffered full batches).
        """
        fetcher = None
        try:
            local_bs = self._local_rows
            tele = self._telemetry

            def prepared():
                for raw in self._reader.iter_batches():
                    if self._stop_event.is_set():
                        return
                    # 'host-assemble' (per RAW reader batch: pad-to-bucket,
                    # field selection) is a distinct stage from 'host-prep'
                    # (per DELIVERED batch in _emit) - one shared name would
                    # mix two granularities and corrupt count/mean/p50
                    with (tele.stage("host-assemble", rows=raw.num_rows,
                                     ordinal=raw.ordinal)
                          if tele.enabled else _NULL_CONTEXT):
                        out = self._prepare(raw)
                    yield out

            if self._split_decode or self._straggler_s is not None:
                if self._straggler_s is not None:
                    fetcher = _TimedSource(prepared(), self._stop_event)
                    next_fn = fetcher.get
                else:
                    gen = prepared()
                    next_fn = lambda _timeout: next(gen)  # noqa: E731
                batches = iter_batched_multi(
                    next_fn, self._form_route, self._make_buffer, local_bs,
                    straggler_release_s=self._straggler_s,
                    on_straggler_release=self._on_straggler_release)
            else:
                batches = iter_batched(prepared(), self._make_buffer(),
                                       local_bs)
            for out in batches:
                if self._stop_event.is_set():
                    break
                if out.num_rows < local_bs and self._drop_last:
                    continue  # partial tail batch dropped
                if self._extent_keys:
                    out = self._pack_mixed(out)
                self._host_push(out)
            self._host_push(_Done())
        except BaseException as exc:  # noqa: BLE001 - forwarded downstream
            self._host_push(_Error(exc))
        finally:
            if fetcher is not None:
                fetcher.join()

    def _transfer(self) -> None:
        """Stage 2: host batches -> device dispatch -> consumer queue.

        In stack mode (``stack_batches=K``) this stage groups K consecutive
        host batches and ships them as ONE ``(K, batch, ...)`` unit; the
        final short group is zero-padded to K steps (``drop_last=False``) or
        dropped (``drop_last=True``, mirroring the row-level semantics).
        """
        group = []
        try:
            while not self._stop_event.is_set():
                try:
                    item = self._host_q.get(timeout=_QUEUE_POLL_S)
                except queue.Empty:
                    continue
                if self._telemetry.enabled:
                    # stamp on the GET side too: a gauge updated only by the
                    # producer freezes at its last (high) value the moment
                    # the producer stalls - inverting the very drain-vs-stall
                    # signal the flight recorder reads it for
                    self._g_host_depth.set(self._host_q.qsize())
                if isinstance(item, _Error):
                    self._push(item)
                    self._sentinel_pending = True
                    self._abort_upstream()
                    return
                if isinstance(item, _Done):
                    break
                if self._stack > 1:
                    group.append(item)
                    if len(group) == self._stack:
                        self._emit_stack(group)
                        group = []
                else:
                    self._emit(item)
            else:
                return  # stopped
            if group and not self._drop_last:
                # partial final stack: zero-pad the missing steps so the
                # consumer's (K, ...) jit signature never changes;
                # '_valid_rows' and the valid mask mark the real rows
                self._emit_stack(group)
            if self._device_buffer is not None:
                for resident in self._device_buffer.drain():
                    if self._stop_event.is_set():
                        break
                    self._push(resident)
                for tail in self._tail_batches:
                    if self._stop_event.is_set():
                        break
                    self._push(tail)
                self._tail_batches = []
            self._push(_Done())
            self._sentinel_pending = True
        except BaseException as exc:  # noqa: BLE001 - forwarded to consumer
            self._push(_Error(exc))
            self._sentinel_pending = True
            self._abort_upstream()

    def _abort_upstream(self) -> None:
        """A producer stage failed terminally: wind down the OTHER producer
        stage, the reader, its executor and ventilator - otherwise (without a
        context manager) the assembly thread would spin on a full host queue
        and the pool would burn wakeups until process exit.  The _Error is
        already in the consumer queue, so ``__next__`` still surfaces it
        (queue drain happens before the stopped-check's StopIteration)."""
        self._stop_event.set()
        try:
            self._reader.stop()
        except Exception:  # noqa: BLE001 - teardown is best-effort
            logger.debug("reader stop during abort failed", exc_info=True)

    def _host_push(self, value) -> None:
        while not self._stop_event.is_set():
            try:
                self._host_q.put(value, timeout=_QUEUE_POLL_S)
                if self._telemetry.enabled:
                    self._g_host_depth.set(self._host_q.qsize())
                return
            except queue.Full:
                continue

    def _prep_cols(self, host_batch: ColumnBatch,
                   pad_to: Optional[int] = None):
        """Per-batch host prep shared by ``_emit`` and ``_emit_stack``:
        extract the deliverable fields, run ``transform_fn``, reject a
        runtime valid-mask collision (the schema collision is caught at
        construction; a transform can still mint the name), and zero-pad
        partial rows to ``pad_to`` (a mesh's static local batch / a stack's
        static per-step shape).  Returns ``(cols, valid_rows)``.

        A live-split field (decode_placement='auto') in HOST form is present
        under its plain name and stages like any pixel field; in device form
        its coefficient planes are handled by the device-decode path."""
        cols = {n: host_batch.columns[n] for n in self._fields
                if n not in self._device_decode
                or (n in self._split_decode and n in host_batch.columns)}
        for name, key in self._extent_keys.items():
            cols[key] = host_batch.columns[_mixed_key(name)].extents
        if self._transform_fn is not None:
            cols = self._transform_fn(cols)
            if self._valid_mask is not None and self._valid_mask in cols:
                raise PetastormTpuError(
                    f"transform_fn produced a field named {self._valid_mask!r},"
                    " which collides with valid_mask_field; rename one")
        valid_rows = host_batch.num_rows
        if pad_to is not None and valid_rows < pad_to:
            # zero-pad to the static row count so the global shape (and the
            # consumer's jit signature) never changes - XLA recompiles per
            # shape, and uneven shards break global assembly
            cols = {name: _pad_rows(col, pad_to)
                    for name, col in cols.items()}
        return cols, valid_rows

    def _emit(self, host_batch: ColumnBatch) -> None:
        tele = self._telemetry
        traced = tele.enabled
        seq = self._emit_seq
        self._emit_seq += 1
        with (tele.stage("host-prep", rows=host_batch.num_rows, batch=seq)
              if traced else _NULL_CONTEXT):
            cols, valid_rows = self._prep_cols(
                host_batch,
                pad_to=self._local_rows if self._mesh is not None else None)
            if self._valid_mask is not None:
                mask = np.zeros(self._local_rows, np.float32)
                mask[:valid_rows] = 1.0
                cols[self._valid_mask] = mask
        transfer_stage = (tele.stage("device-transfer", rows=valid_rows,
                                     batch=seq)
                          if traced else _NULL_CONTEXT)
        with transfer_stage:
            device_batch = {}
            sent: List[jax.Array] = []   # decode inputs copied from the host
            for name in self._device_decode:
                if name in self._fields and not (
                        name in self._split_decode
                        and name in host_batch.columns):
                    # (a live-split field in host form is already in `cols`
                    # as pixels and stages below like any other field)
                    decode = (self._decode_mixed_on_device
                              if name in self._mixed_decode
                              else self._decode_on_device)
                    with (tele.span("device-decode", batch=seq)
                          if traced else _NULL_CONTEXT):
                        device_batch[name], inputs = decode(
                            name, host_batch.columns)
                    sent.extend(inputs)
            finishes = len(device_batch)
            with tele.span("put", batch=seq) if traced else _NULL_CONTEXT:
                put: Dict[str, jax.Array] = {}
                staged: Dict[str, np.ndarray] = {}
                for name, col in cols.items():
                    arr = np.ascontiguousarray(col)
                    feed_dtype = jax_feed_dtype(arr.dtype,
                                                keep_wide=self._keep_wide)
                    if arr.dtype != feed_dtype:
                        arr = arr.astype(feed_dtype)
                    self._emitted_layout[name] = (arr.shape[1:], arr.dtype)
                    if self._mesh is not None:
                        sharding, sl, global_shape = self._placement_for(
                            name, arr.shape[1:])
                        # sequence/model-axis slice
                        arr = arr[(slice(None),) + sl[1:]]
                        put[name] = jax.make_array_from_process_local_data(
                            sharding, arr, global_shape)
                    else:
                        staged[name] = arr
                if staged:
                    # ONE device_put for all fields: each call pays a fixed
                    # dispatch cost, so a small label column must not cost
                    # as much as the image column it rides with
                    put.update(jax.device_put(staged))
            device_batch.update(put)
            sent.extend(put.values())
            self._commit(sent, seq, finishes)
        for name in self._host_fields:
            device_batch[name] = host_batch.columns[name]
        if self._mesh is not None and valid_rows < self._local_rows:
            device_batch["_valid_rows"] = valid_rows
        if self._device_buffer is not None:
            if valid_rows == self._local_rows:
                out = self._device_buffer.push(device_batch)
                if out is not None:
                    self._push(out)
            else:
                # partial tail batch (different shape / '_valid_rows') cannot
                # enter the HBM buffer; stash it so it is still emitted LAST,
                # after the drain - consumers treat it as the epoch-end signal
                self._tail_batches.append(device_batch)
            return
        self._push(device_batch)

    def _emit_stack(self, group) -> None:
        """Stack-mode emit: K consecutive host batches -> ONE delivered unit
        of ``(K, batch, ...)`` device arrays, shipped in a single transfer.

        Per-step semantics match ``_emit`` exactly (transform_fn runs per
        batch BEFORE stacking, dtype promotion once on the stacked array).
        A short group (epoch end / drain with ``drop_last=False``) zero-pads
        the missing steps; partial row batches zero-pad their rows - in both
        cases ``'_valid_rows'`` becomes a per-step int array and the valid
        mask (shape ``(K, batch)``) marks the real rows, so a ``lax.scan``
        consumer runs all K steps with a constant signature and weights by
        the mask (the pod-safe pattern, see ``drain()``).
        """
        K, local = self._stack, self._local_rows
        real_steps = len(group)
        tele = self._telemetry
        traced = tele.enabled
        seq = self._emit_seq
        self._emit_seq += 1
        prepped, valids = [], []
        with (tele.stage("host-prep", steps=real_steps, batch=seq)
              if traced else _NULL_CONTEXT):
            for hb in group:
                # pad even without a mesh: the (K, B, ...) stack needs one
                # static per-step shape
                cols, valid = self._prep_cols(hb, pad_to=local)
                prepped.append(cols)
                valids.append(valid)

        transfer_stage = (tele.stage("device-transfer", steps=real_steps,
                                     batch=seq)
                          if traced else _NULL_CONTEXT)
        with transfer_stage:
            device_batch = {}
            sent: List[jax.Array] = []   # decode inputs copied from the host
            for name in self._device_decode:
                if name in self._fields:
                    decode = (self._decode_mixed_stack
                              if name in self._mixed_decode
                              else self._decode_stack)
                    with (tele.span("device-decode", batch=seq)
                          if traced else _NULL_CONTEXT):
                        device_batch[name], inputs = decode(name, group)
                    sent.extend(inputs)
            finishes = len(device_batch)

            with tele.span("put", batch=seq) if traced else _NULL_CONTEXT:
                put: Dict[str, jax.Array] = {}
                staged: Dict[str, np.ndarray] = {}
                for name in (list(prepped[0]) if prepped else []):
                    steps = [np.ascontiguousarray(p[name]) for p in prepped]
                    steps += [np.zeros_like(steps[-1])] * (K - real_steps)
                    arr = np.stack(steps)              # (K, local, *trailing)
                    feed_dtype = jax_feed_dtype(arr.dtype,
                                                keep_wide=self._keep_wide)
                    if arr.dtype != feed_dtype:
                        arr = arr.astype(feed_dtype)
                    self._emitted_layout[name] = (arr.shape[2:], arr.dtype)
                    if self._mesh is not None:
                        sharding, sl, global_shape = self._placement_for(
                            name, arr.shape[2:])
                        arr = arr[(slice(None), slice(None)) + sl[2:]]
                        put[name] = jax.make_array_from_process_local_data(
                            sharding, arr, global_shape)
                    else:
                        staged[name] = arr
                if self._valid_mask is not None:
                    mask = np.zeros((K, local), np.float32)
                    for k, v in enumerate(valids):
                        mask[k, :v] = 1.0
                    name = self._valid_mask
                    self._emitted_layout[name] = ((), np.dtype(np.float32))
                    sharding, _, global_shape = self._placement_for(name, ())
                    put[name] = jax.make_array_from_process_local_data(
                        sharding, mask, global_shape)
                if staged:
                    # ONE device_put for the whole stack: K steps of data
                    # ride a single fixed-cost dispatch instead of K (the
                    # whole point)
                    put.update(jax.device_put(staged))
            device_batch.update(put)
            sent.extend(put.values())
            self._commit(sent, seq, finishes)
        for name in self._host_fields:
            steps = [_pad_host_col(hb.columns[name], local) for hb in group]
            steps += [_host_filler(steps[-1])] * (K - real_steps)
            device_batch[name] = np.stack(steps)
        if real_steps < K or any(v < local for v in valids):
            device_batch["_valid_rows"] = np.asarray(
                valids + [0] * (K - real_steps), dtype=np.int64)
        self._push(device_batch)

    def _commit(self, sent: List[jax.Array], seq: int,
                finishes: int) -> None:
        """Commit the transfers in the transfer thread: the consumer then
        never blocks on a half-copied array, and its readiness query never
        queues behind the next batch's dispatch (serialized device RPC
        channels would otherwise surface that contention as input stall).

        What is committed is ``sent``: the arrays this batch copied from the
        host - the put fields, the valid mask, and the coefficient planes
        and quantisation tables the on-chip JPEG finish reads.  The
        ``finishes`` fields computed on the chip from them (the finish's
        output and whatever is derived from it there) are NOT waited on: the
        device's stream order already runs a batch's finish before the step
        that reads it, and waiting on it would hold the transfer thread
        until the step already in flight ends - the feed would then never
        run ahead of the step.  A fault inside the on-chip finish therefore
        surfaces where the consumer uses the array, not here.

        DEFAULT (transfer_commit='auto'): the readiness-sync cost is probed
        ONCE before the first commit - one warm trivial op timed three times
        - and on runtimes where a sync is expensive (the probe threshold is
        20 ms for a nanosecond-scale op), async-chained transfer becomes the
        default from batch 1 instead of a mid-run discovery after two
        breaches.

        ADAPTIVE backstop: a runtime whose syncs turn expensive mid-session
        would cap delivery at a few batches/s.  When a commit costs far more
        than the data volume can explain, committing is permanently disabled
        for this loader: async dispatch chains device-side, so consumers pay
        waits only at genuine use points, which pipelines strictly better on
        such runtimes.
        Correctness is unaffected either way.
        """
        if not self._commit_probed:
            self._probe_commit_cost()
        if not self._commit_transfers:
            return
        tele = self._telemetry
        t0 = time.perf_counter()
        # the 'commit' stage: the transfer thread waiting on the batch's
        # host->device copies
        with (tele.stage("commit", batch=seq) if tele.enabled
              else _NULL_CONTEXT):
            jax.block_until_ready(sent)
        took = time.perf_counter() - t0
        if finishes:
            self._m_transfer_only.add(1)
        self._commit_count += 1
        if self._commit_count == 1:
            return  # first commit carries one-time executable warmup cost
        nbytes = sum(v.nbytes for v in sent)
        # generous floor: 100 MB/s sustained transfer + 100 ms fixed is
        # slower than any healthy runtime; beyond it the sync itself is the
        # cost, not the copy.  Two CONSECUTIVE breaches are required so a
        # single GC/scheduler hiccup cannot permanently disable commits on
        # a healthy runtime (consumers would then block on un-landed arrays
        # and producer-side transfer errors would surface at use instead)
        if took > 0.1 + nbytes / 100e6:
            self._commit_breaches += 1
            if self._commit_breaches >= 2:
                self._commit_transfers = False
                logger.warning(
                    "transfer commit took %.0f ms for %.1f MB (twice in a"
                    " row) - this runtime charges a round trip per readiness"
                    " sync; disabling per-batch commit (async chaining takes"
                    " over)", took * 1e3, nbytes / 1e6)
        else:
            self._commit_breaches = 0

    def _probe_commit_cost(self) -> None:
        """transfer_commit='auto': measure a trivial readiness sync (min of
        3 after one warmup) in the transfer thread, before the first batch
        commits.  A runtime charging a round trip per sync starts in
        async-chained mode immediately; the per-batch adaptive breach logic
        stays armed either way as the backstop."""
        self._commit_probed = True
        try:
            jax.block_until_ready(jax.device_put(1.0))  # warmup/backend init
            costs = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(jax.device_put(1.0))
                costs.append(time.perf_counter() - t0)
            cost = min(costs)
        except Exception:  # noqa: BLE001 - a probe failure must not break ingest
            logger.debug("transfer-commit probe failed; keeping commits on",
                         exc_info=True)
            return
        self._commit_probe_ms = cost * 1e3
        if cost > _COMMIT_PROBE_THRESHOLD_S:
            self._commit_transfers = False
            logger.info(
                "readiness sync costs %.0f ms for a trivial op - this runtime"
                " charges a round trip per sync; defaulting to async-chained"
                " transfer (no per-batch commit). transfer_commit=True"
                " overrides.", cost * 1e3)

    def _decode_stack(self, name: str, group
                      ) -> Tuple[jax.Array, List[jax.Array]]:
        """Stack-mode variant of ``_decode_on_device``: the K batches'
        coefficient planes ship as ONE ``(K, local, ...)`` transfer and the
        on-chip dequant+IDCT+upsample+color runs once over the whole stack
        (``ops/jpeg.decode_coefficients`` handles leading batch dims).
        Returns the decoded field and the arrays copied from the host."""
        from petastorm_tpu.native.image import unpack_coef_columns
        from petastorm_tpu.ops.jpeg import decode_coefficients

        K, local = self._stack, self._local_rows
        per = [unpack_coef_columns(name, hb.columns) for hb in group]
        layout0 = per[0][2]
        for _, _, lay in per[1:]:
            if ((lay.height, lay.width, lay.components)
                    != (layout0.height, layout0.width, layout0.components)):
                raise PetastormTpuError(
                    f"field {name!r}: jpeg geometry changed between stacked"
                    " batches - decode_placement='device' requires one"
                    " geometry dataset-wide (use 'device-mixed')")

        stacked_planes = []
        for c in range(len(layout0.components)):
            steps = [_pad_rows(planes[c], local) for planes, _, _ in per]
            steps += [np.zeros_like(steps[-1])] * (K - len(per))
            stacked_planes.append(np.stack(steps))   # (K, local, bh, bw, 64)
        qt_steps = [_pad_rows(qtabs, local, fill=1) for _, qtabs, _ in per]
        qt_steps += [np.ones_like(qt_steps[-1])] * (K - len(per))
        jqt = np.stack(qt_steps)                     # (K, local, ncomp, 64)
        sampling = tuple((h, v) for (h, v, _, _) in layout0.components)
        field = self._schema[name]
        if self._mesh is None:
            jp, jq = jax.device_put((tuple(stacked_planes), jqt))
            out = decode_coefficients(
                jp, jq, image_size=(layout0.height, layout0.width),
                sampling=sampling)
        else:
            spec = self._spec_for(name)
            batch_sharding = NamedSharding(
                self._mesh,
                PartitionSpec(None, spec[0] if len(spec) else None))
            jp = tuple(jax.make_array_from_process_local_data(
                batch_sharding, p, (K, self._global_batch) + p.shape[2:])
                for p in stacked_planes)
            jq = jax.make_array_from_process_local_data(
                batch_sharding, jqt, (K, self._global_batch) + jqt.shape[2:])
            out = decode_coefficients(
                jp, jq, image_size=(layout0.height, layout0.width),
                sampling=sampling)
            if any(ax is not None for ax in spec[1:]):
                out = jax.device_put(
                    out, NamedSharding(self._mesh, PartitionSpec(None, *spec)))
        if len(field.shape) == 3 and field.shape[2] == 1 and out.ndim == 4:
            out = out[..., None]  # honor a declared (H, W, 1) grayscale shape
        return out, [*jp, jq]

    def _decode_mixed_stack(self, name: str, group
                            ) -> Tuple[jax.Array, List[jax.Array]]:
        """Stack-mode variant of ``_decode_mixed_on_device``: the K batches'
        canvas groups finish into one flat ``K*local``-row batch (host-local,
        as ever), then reshape to ``(K, local, ...)`` and scatter along the
        batch axis.  Returns the decoded field and the arrays copied from
        the host."""
        K, local = self._stack, self._local_rows
        packs = [(k * local, hb.columns[_mixed_key(name)])
                 for k, hb in enumerate(group)]
        out, sent = self._decode_mixed_flat(name, packs, K * local)
        out = out.reshape((K, local) + out.shape[1:])
        if self._mesh is not None:
            out = self._scatter_stacked_rows(name, out)
        return out, sent

    def _scatter_stacked_rows(self, name: str, out) -> jax.Array:
        """(K, local, ...) host-local decoded rows -> one global mesh array
        of shape (K, global, ...); the stack axis is unsharded, the batch
        axis scatters exactly like ``_scatter_local_rows``."""
        return self._scatter_batch_axis(name, out, lead=1)

    def _decode_mixed_on_device(self, name: str, columns: Dict[str, np.ndarray]
                                ) -> Tuple[jax.Array, List[jax.Array]]:
        """Finish the hybrid decode of a MIXED-geometry field
        (decode_placement='device-mixed') from the canvas groups the
        assembly thread packed (``_pack_mixed``), then scatter the rows over
        the mesh.  Returns the decoded field and the arrays copied from the
        host."""
        pack = columns[_mixed_key(name)]
        n = len(pack)
        out, sent = self._decode_mixed_flat(name, [(0, pack)],
                                            max(self._local_rows, n))
        if self._mesh is not None:
            out = self._scatter_local_rows(name, out, n)
        return out, sent

    def _decode_mixed_flat(self, name: str, packs, n_out: int
                           ) -> Tuple[jax.Array, List[jax.Array]]:
        """Finish ``packs`` (``(row offset, _MixedPack)`` pairs) into one
        ``(n_out, *target)`` uint8 batch on the default device: one on-chip
        call per canvas group, each writing its images into their rows (the
        decode is host-local; mesh placement happens after).  Returns the
        batch and the arrays copied from the host: each group's planes,
        tables, extents and rows, copied in one ``device_put``."""
        import jax.numpy as jnp

        target = self._mixed_target(name)
        groups, host = [], []
        for offset, pack in packs:
            for g in pack.groups:
                rows = np.where(g.rows < 0, n_out, g.rows + offset)
                groups.append(g)
                host.append((g.planes, g.qtabs, g.extents,
                             rows.astype(np.int32)))
        out = jnp.zeros((n_out,) + target, jnp.uint8)
        sent: List[jax.Array] = []
        for g, (jp, jq, je, jr) in zip(groups, jax.device_put(host)):
            out = self._finish_program(name, g, n_out)(out, jp, jq, je, jr)
            sent += [*jp, jq, je, jr]
        return out, sent

    def _mixed_group_sizes(self) -> Tuple[int, ...]:
        return tuple(sorted({min(s, self._local_rows)
                             for s in _MIXED_GROUP_SIZES}))

    def _finish_program(self, name: str, group: "_CanvasGroup", n_out: int):
        """The compiled on-chip finish of ``group``'s (chroma layout,
        canvas, group size).  A layout's first group compiles the programs
        of EVERY canvas and group size of the field, so nothing compiles
        after the batch where a layout first shows: the count
        (``loader.mixed_finish_programs``) is layouts x canvases x group
        sizes, whatever the geometries."""
        from petastorm_tpu.ops.jpeg import finish_canvas

        key = (name, group.sampling, group.canvas, len(group.rows))
        program = self._finish_programs.get(key)
        if program is not None:
            return program
        target = self._mixed_target(name)
        sds = jax.ShapeDtypeStruct
        for canvas in self._mixed_canvases(name):
            for size in self._mixed_group_sizes():
                planes = tuple(
                    sds((size,) + shape + (64,), np.int16) for shape
                    in _canvas_plane_shapes(canvas, group.sampling))
                self._finish_programs[(name, group.sampling, canvas, size)] = (
                    finish_canvas.lower(
                        sds((n_out,) + target, np.uint8), planes,
                        sds((size, len(planes), 64), np.uint16),
                        sds((size, 2), np.int32), sds((size,), np.int32),
                        canvas_hw=canvas, sampling=group.sampling).compile())
                self._m_mixed_programs.add(1)
        return self._finish_programs[key]

    def _pack_mixed(self, batch: ColumnBatch) -> ColumnBatch:
        """The 'mixed-pack' stage (assembly thread): each 'device-mixed'
        field's object cells become canvas groups (``_pack_canvases``)."""
        tele = self._telemetry
        cols = dict(batch.columns)
        with (tele.stage("mixed-pack", rows=batch.num_rows)
              if tele.enabled else _NULL_CONTEXT):
            for name in self._extent_keys:
                key = _mixed_key(name)
                cols[key] = self._pack_canvases(name, cols[key])
        return dataclasses.replace(batch, columns=cols)

    def _pack_canvases(self, name: str, col) -> "_MixedPack":
        """Pad each image's coefficient planes top-left into the smallest
        canvas that holds it.  Per chroma layout and canvas, the images go
        in groups of the largest of ``_MIXED_GROUP_SIZES``, the rest in the
        smallest size that holds them, padded with rows the finish drops."""
        from petastorm_tpu.native.image import _layout_from_meta

        n = len(col)
        keys = [cell[2].tobytes() for cell in col]
        seen = self._mixed_geometries.setdefault(name, {})
        for key in set(keys) - seen.keys():
            seen[key] = _layout_from_meta(np.frombuffer(key, dtype=np.int32))
            self._check_declared_geometry(name, seen[key])
        layouts = [seen[k] for k in keys]
        extents = np.asarray([(lay.height, lay.width) for lay in layouts],
                             np.int32).reshape(n, 2)
        canvases = self._mixed_canvases(name)
        ch = np.asarray([c[0] for c in canvases])
        cw = np.asarray([c[1] for c in canvases])
        fits = ((extents[:, :1] <= ch[None]) & (extents[:, 1:] <= cw[None]))
        if not fits[:, -1].all():
            i = int(np.argmin(fits[:, -1]))
            raise PetastormTpuError(
                f"field {name!r}: a stored jpeg of {tuple(extents[i])}"
                f" (height, width) does not fit the largest canvas"
                f" {canvases[-1]}; add a larger pad_shapes canvas")
        canvas_of = fits.argmax(axis=1)   # canvases run smallest first
        target = self._mixed_target(name)
        by_sampling: Dict[tuple, List[int]] = {}
        for i, lay in enumerate(layouts):
            ncomp = len(lay.components)
            if ncomp not in (1, 3) or (ncomp == 3 and (
                    len(target) == 2 or target[2] != 3)):
                raise PetastormTpuError(
                    f"field {name!r}: a stored jpeg has {ncomp} colour"
                    f" components, which the delivered shape {target}"
                    " cannot hold; store grayscale or RGB jpegs and declare"
                    " (H, W) or (H, W, 3)")
            sampling = tuple((h, v) for (h, v, _, _) in lay.components)
            by_sampling.setdefault(sampling, []).append(i)
        sizes = self._mixed_group_sizes()
        groups = []
        canvas_px = 0
        for sampling, idx in by_sampling.items():
            idx = np.asarray(idx)
            for c, canvas in enumerate(canvases):
                members = idx[canvas_of[idx] == c]
                for a in range(0, len(members), sizes[-1]):
                    part = members[a:a + sizes[-1]]
                    size = next(s for s in sizes if s >= len(part))
                    groups.append(_CanvasGroup.pack(col, part, extents, canvas,
                                                    sampling, size))
                canvas_px += len(members) * canvas[0] * canvas[1]
        self._m_mixed_valid.add(float(np.prod(extents, axis=1).sum()))
        self._m_mixed_canvas.add(float(canvas_px))
        return _MixedPack(extents, groups)

    def _check_canvases_hold_declared(self, name: str) -> None:
        """Construction-time check of a 'device-mixed' field: it has
        canvases, and the largest holds every geometry the dataset's
        stamped contract declares."""
        largest = self._mixed_canvases(name)[-1]
        too_big = sorted({(int(s[0]), int(s[1]))
                          for s in self._declared_geometries.get(name, ())
                          if s[0] > largest[0] or s[1] > largest[1]})
        if too_big:
            raise PetastormTpuError(
                f"field {name!r}: the dataset declares image geometries"
                f" {too_big[:5]} (height, width) larger than the largest"
                f" canvas {largest}; add a pad_shapes canvas that holds them")

    def _check_declared_geometry(self, name: str, layout) -> None:
        """Warn (once per geometry) when a batch reveals an image geometry
        missing from the dataset-level contract stamped at write time - the
        compile count is then no longer bounded by the declared set."""
        shapes = self._declared_geometries.get(name)
        if not shapes:
            return  # no contract stamped (e.g. externally-written dataset)
        # channel count matters too: a grayscale jpeg at a declared color
        # size is still a NEW decode compile (the contract is shape-level;
        # subsampling variants within one shape are beyond its resolution)
        hwc = {(s[0], s[1], s[2] if len(s) > 2 else 1) for s in shapes}
        seen = (layout.height, layout.width, len(layout.components))
        key = (name,) + seen
        if seen not in hwc and key not in self._geom_warned:
            self._geom_warned.add(key)
            logger.warning(
                "field %r: jpeg geometry %s (h, w, channels) is not in the"
                " dataset's declared geometry contract %s - the on-device"
                " decode compile count is no longer bounded by the declared"
                " set; re-stamp it (petastorm-tpu-generate-metadata"
                " --scan-geometries) after changing the dataset",
                name, seen, sorted(hwc))

    def _scatter_local_rows(self, name: str, out, n: int) -> jax.Array:
        """Host-local decoded rows -> one GLOBAL mesh array.

        The mixed-geometry decode is deliberately host-local: each host
        compiles kernels only for the geometries IT encountered (the
        dataset-level contract stamped at write time -
        ``etl.metadata.declared_geometries`` - bounds the total), and bucket
        sets may differ across hosts without any cross-host agreement,
        because no collective runs inside the decode.  Mesh delivery is then
        pure data placement: zero-pad to the static local row count, split
        across this host's addressable devices, and declare the result a
        global array (``jax.make_array_from_single_device_arrays`` - no
        collective, no host round-trip of the decoded pixels).
        """
        import jax.numpy as jnp

        if n < self._local_rows:
            out = jnp.concatenate(
                [out, jnp.zeros((self._local_rows - n,) + out.shape[1:],
                                out.dtype)])
        return self._scatter_batch_axis(name, out, lead=0)

    def _scatter_batch_axis(self, name: str, out, lead: int) -> jax.Array:
        """Shared scatter: a host-local array whose batch axis sits at
        position ``lead`` (0 = plain batch, 1 = stacked ``(K, local, ...)``)
        becomes one global mesh array; any leading axes stay unsharded.
        The construction-time contract (``_validate_mixed_scatter_layout``)
        guarantees the addressable shards tile one contiguous block."""
        spec = self._spec_for(name)
        sharding = NamedSharding(
            self._mesh,
            PartitionSpec(*((None,) * lead),
                          spec[0] if len(spec) else None))
        global_shape = (tuple(out.shape[:lead]) + (self._global_batch,)
                        + tuple(out.shape[lead + 1:]))
        idx_map = sharding.addressable_devices_indices_map(global_shape)
        starts = [(sl[lead].start or 0) for sl in idx_map.values()]
        lo = min(starts)
        prefix = (slice(None),) * lead
        shards = []
        for dev, sl in idx_map.items():
            a = (sl[lead].start or 0) - lo
            b = (sl[lead].stop if sl[lead].stop is not None
                 else global_shape[lead]) - lo
            shards.append(jax.device_put(out[prefix + (slice(a, b),)], dev))
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards)

    def _decode_on_device(self, name: str, columns: Dict[str, np.ndarray]
                          ) -> Tuple[jax.Array, List[jax.Array]]:
        """Finish the hybrid jpeg decode of one field (decode_placement='device').

        The entropy half already ran in the pool workers - ``columns`` holds
        the field's derived coefficient-plane columns ('<name>#...', see
        native/image.py pack_coef_columns).  Here the planes ship to the
        device(s) batch-sharded and the FLOP-heavy dequant + IDCT + upsample +
        color runs on-chip, sharded, with no cross-shard communication
        (petastorm_tpu/ops/jpeg.py).  Returns the decoded field and the
        arrays copied from the host (the planes and quantisation tables).
        """
        from petastorm_tpu.native.image import unpack_coef_columns
        from petastorm_tpu.ops.jpeg import decode_coefficients

        field = self._schema[name]
        # (shape vs schema was already checked worker-side in pack_coef_columns)
        planes, qtabs, layout = unpack_coef_columns(name, columns)
        sampling = tuple((h, v) for (h, v, _, _) in layout.components)
        n = len(qtabs)
        if self._mesh is None:
            # one batched transfer for all planes + qtabs (fixed dispatch
            # cost per device_put call), then the on-chip half
            jp, jq = jax.device_put((tuple(planes), qtabs))
            out = decode_coefficients(jp, jq,
                                      image_size=(layout.height, layout.width),
                                      sampling=sampling)
        else:
            if n < self._local_rows:
                # zero coefficient blocks decode to flat gray padding rows
                # ('_valid_rows' marks how many are real, as for host fields)
                planes = [_pad_rows(p, self._local_rows) for p in planes]
                qtabs = _pad_rows(qtabs, self._local_rows, fill=1)
            spec = self._spec_for(name)
            batch_sharding = NamedSharding(
                self._mesh, PartitionSpec(spec[0] if len(spec) else None))
            jp = tuple(jax.make_array_from_process_local_data(
                batch_sharding, p, (self._global_batch,) + p.shape[1:])
                for p in planes)
            jq = jax.make_array_from_process_local_data(
                batch_sharding, qtabs, (self._global_batch,) + qtabs.shape[1:])
            out = decode_coefficients(jp, jq,
                                      image_size=(layout.height, layout.width),
                                      sampling=sampling)
            if any(ax is not None for ax in spec[1:]):
                # user sharded trailing image axes too: reshard once on device
                out = jax.device_put(out, NamedSharding(self._mesh, spec))
        if len(field.shape) == 3 and field.shape[2] == 1 and out.ndim == 3:
            out = out[..., None]  # honor a declared (H, W, 1) grayscale shape
        return out, [*jp, jq]

    def _delivery_spec(self, name: str) -> PartitionSpec:
        """The PartitionSpec a delivered array for ``name`` actually uses:
        the user's spec, with an unsharded leading stack axis prepended in
        stack mode (the K stacked batches ride the same devices their rows
        would ride individually)."""
        spec = self._spec_for(name)
        if self._stack > 1:
            return PartitionSpec(None, *spec)
        return spec

    def _delivery_global(self, trailing: Tuple[int, ...]) -> Tuple[int, ...]:
        """Global shape of a delivered array with per-row ``trailing`` dims."""
        lead = (self._stack,) if self._stack > 1 else ()
        return lead + (self._global_batch,) + trailing

    def _placement_for(self, name: str, trailing: Tuple[int, ...]
                       ) -> Tuple[NamedSharding, Tuple[slice, ...], Tuple[int, ...]]:
        key = (name, trailing)
        hit = self._placement_cache.get(key)
        global_shape = self._delivery_global(trailing)
        if hit is None:
            sharding = NamedSharding(self._mesh, self._delivery_spec(name))
            sl = local_data_slice(sharding, global_shape)
            hit = (sharding, sl)
            self._placement_cache[key] = hit
        return hit[0], hit[1], global_shape

    def _push(self, value) -> None:
        while not self._stop_event.is_set():
            try:
                self._out.put(value, timeout=_QUEUE_POLL_S)
                return
            except queue.Full:
                continue

    # -- consumer -------------------------------------------------------------

    @property
    def telemetry(self):
        """The pipeline telemetry recorder this loader records into (the
        reader's by default; petastorm_tpu.telemetry)."""
        return self._telemetry

    @property
    def diagnostics(self) -> Dict:
        """Per-stage queue depths + reader diagnostics (SURVEY.md section 5:
        the TPU build's observability story).  ``prefetch_depth`` near
        capacity = host pipeline keeps up; near 0 = device is input-bound."""
        depth = self._out.qsize()
        if self._sentinel_pending:  # end-of-stream marker is not a batch
            depth = max(depth - 1, 0)
        out = {"prefetch_depth": depth,
               "prefetch_capacity": self._out.maxsize,
               "host_queue_depth": self._host_q.qsize(),
               "delivered_batches": self._delivered_batches,
               "consumer_wait_s": self._consumer_wait_s,
               # batches released past the shuffle floor because the source
               # straggled (straggler_release_s), and the transfer-commit
               # verdict (False = async-chained; probe cost when measured)
               "straggler_releases": self._straggler_releases,
               "transfer_commit": self._commit_transfers,
               "transfer_commit_probe_ms": self._commit_probe_ms,
               "finished": self._finished,
               # producer threads that missed the stop() join budget (each
               # {thread, stage}); non-empty = the shutdown was not clean
               "unquiesced_threads": list(self._unquiesced)}
        if self._stack > 1:
            out["stack_batches"] = self._stack
        if self._mixed_geometries:
            # distinct jpeg geometries per 'device-mixed' field, and the
            # on-chip finish programs compiled for them: chroma layouts x
            # canvases x group sizes, however many geometries
            out["mixed_decode_geometries"] = {
                name: len(keys) for name, keys in self._mixed_geometries.items()}
            out["mixed_finish_programs"] = len(self._finish_programs)
            if self._declared_geometries:
                # the dataset-level bound those counts must stay under
                out["declared_geometries"] = {
                    name: len(shapes)
                    for name, shapes in self._declared_geometries.items()}
        reader_diag = getattr(self._reader, "diagnostics", None)
        if isinstance(reader_diag, dict):
            out["reader"] = reader_diag
            if reader_diag.get("skipped_rowgroups"):
                # fault ledger surfaced at the loader level too: a feed that
                # is degraded-but-running under an on_error skip policy must
                # be visible without digging into the nested reader dict
                out["skipped_rowgroups"] = reader_diag["skipped_rowgroups"]
                out["quarantined_rowgroups"] = reader_diag.get(
                    "quarantined_rowgroups", [])
        return out

    def __iter__(self):
        if not self._started:
            self._started = True
            self._thread.start()
            self._transfer_thread.start()
            if self._trace_dir:
                try:
                    jax.profiler.start_trace(self._trace_dir)
                    self._tracing = True
                except (RuntimeError, OSError) as exc:
                    # another trace already active process-wide, or unwritable
                    # dir: iterate untraced rather than fail the ingest
                    logger.warning("trace_dir=%r: could not start jax trace:"
                                   " %s", self._trace_dir, exc)
        return self

    def __next__(self) -> Dict[str, jax.Array]:
        if self._failure is not None:
            raise self._failure
        if self._finished:
            raise StopIteration  # repeatable after exhaustion (iterator protocol)
        if not self._started:
            iter(self)
        wait_start = time.perf_counter()
        tele = self._telemetry
        # 'consumer-wait' spans only a get that finds the queue empty
        with (tele.span("consumer-wait", batch=self._delivered_batches)
              if tele.enabled and self._out.empty() else _NULL_CONTEXT):
            value = self._next_value(wait_start)
        if isinstance(value, _Done):
            self._finished = True
            self._sentinel_pending = False
            self._stop_trace()  # exhaustion flushes the trace without stop()
            raise StopIteration
        if isinstance(value, _Error):
            self._failure = value.exc
            self._sentinel_pending = False
            self._stop_trace()
            raise value.exc
        self._delivered_batches += 1
        self._m_delivered.add(1)
        return value

    def _next_value(self, wait_start: float):
        """Block for the consumer queue's next value (a batch or a
        sentinel), accounting the wait as consumer wait time."""
        while True:
            try:
                value = self._out.get(timeout=_QUEUE_POLL_S)
                waited = time.perf_counter() - wait_start
                self._consumer_wait_s += waited
                if self._telemetry.enabled:
                    self._m_consumer_wait.add(waited)
                    self._g_prefetch_depth.set(self._out.qsize())
                return value
            except queue.Empty:
                if self._stop_event.is_set():
                    self._finished = True
                    raise StopIteration
                if not self._transfer_thread.is_alive():
                    # the producer may have pushed its sentinel between our
                    # timeout and this liveness check - drain before concluding
                    try:
                        return self._out.get_nowait()
                    except queue.Empty:
                        self._failure = PetastormTpuError(
                            "Loader producer thread died silently")
                        self._stop_trace()
                        raise self._failure

    # -- checkpoint/resume (reference gap: SURVEY.md section 5) ---------------

    def drain(self, all_gather_counts=None):
        """Quiesce ingest and return an iterator over every in-flight batch;
        afterwards the loader is exhausted and ``state_dict()`` is an EXACT
        cursor (zero re-read rows on resume) even with thread/process pools
        and a device shuffle buffer active.

        The preemption-checkpoint flow on a TPU pod::

            for batch in loader.drain():   # train on what's already in flight
                step(batch)
            save(loader.state_dict())      # exact - no re-reads on restart

        Quiesce happens EAGERLY in this call (not on first ``next``), so the
        returned iterator must be consumed before ``state_dict()`` for the
        exactness guarantee - an unconsumed drain leaves batches undelivered,
        which ``state_dict()``'s re-read window then covers as usual.

        Multi-host: each host freezes its pipeline at a timing-dependent
        point, so hosts drain UNEQUAL batch counts - if ``step`` runs
        pod-wide collectives, the pod would hang.  On a mesh with
        ``jax.process_count() > 1`` the hosts therefore agree on the maximum
        drained count (one small all-gather) and the shorter ones pad with
        zero batches carrying ``'_valid_rows': 0`` - every host yields the
        same number of steps.  ``all_gather_counts`` overrides the collective
        (tests; custom coordination).

        ``'_valid_rows'`` is HOST-LOCAL: the same drained step can be a real
        batch on one host and a pad on another, so a consumer that branches
        on it (``if _valid_rows == 0: continue``) diverges control flow
        across the pod and hangs the very collective drain exists to protect.
        Multi-host consumers must instead construct the loader with
        ``valid_mask_field=`` and run EVERY drained step, weighting the loss
        by the mask - a globally-consistent device array (1.0 real row / 0.0
        pad) assembled like any data field.  Proven for real (separate OS
        processes, Gloo collectives) by
        ``petastorm_tpu.parallel.selfcheck`` and
        ``tests/test_multiprocess_distributed.py``.

        With ``drop_last=True`` a final partial batch's rows are dropped
        exactly as they would be at an epoch end - and in stack mode
        (``stack_batches=K``) the accumulating short stack is dropped too,
        discarding up to K-1 FULL batches whose rows the reader cursor has
        already passed.  Training that checkpoints mid-epoch should use
        ``drop_last=False`` (mesh consumers get zero-padded ``'_valid_rows'``
        tails; stack consumers get a zero-padded final stack with per-step
        counts).
        """
        if not hasattr(self._reader, "quiesce"):
            raise PetastormTpuError(
                f"Reader {type(self._reader).__name__} does not support"
                " quiesce(); drain-to-cursor needs a petastorm_tpu Reader")
        self._reader.quiesce()

        multihost = self._mesh is not None and (
            all_gather_counts is not None or jax.process_count() > 1)
        if not multihost:
            def _local():
                while True:
                    try:
                        yield next(self)
                    except StopIteration:
                        return
            return _local()

        # multi-host: drain locally first (bounded by the in-flight window),
        # agree on the pod-wide max, pad the difference so every host steps
        # the same number of times
        local = []
        while True:
            try:
                local.append(next(self))
            except StopIteration:
                break
        if all_gather_counts is None:
            from jax.experimental import multihost_utils

            counts = multihost_utils.process_allgather(
                np.asarray([len(local)], dtype=np.int32))
            target = int(np.max(counts))
        else:
            target = int(max(all_gather_counts(len(local))))

        def _zero_array(global_shape, sharding, dtype):
            # zeros with the SAME global shape and sharding so collectives in
            # the consumer's step see identically laid-out operands; allocate
            # only shard-sized zeros (a global-shape buffer per shard would
            # spike host memory exactly at preemption time)
            shard_shape = sharding.shard_shape(global_shape)
            return jax.make_array_from_callback(
                global_shape, sharding,
                lambda idx, _s=shard_shape, _d=dtype: np.zeros(_s, _d))

        def _aligned():
            template = local[-1] if local else None
            synthesized = None
            for batch in local:
                yield batch
            for _ in range(target - len(local)):
                pad = {}
                if template is None and synthesized is None:
                    # derived lazily: when target == 0 no pad is needed and
                    # fields whose shapes cannot be derived must not raise
                    synthesized = self._pad_batch_layout()
                if template is not None:
                    for name, value in template.items():
                        if name == "_valid_rows":
                            continue
                        if isinstance(value, jax.Array):
                            pad[name] = _zero_array(value.shape, value.sharding,
                                                    value.dtype)
                        else:
                            pad[name] = value  # host fields pass through
                else:
                    # this host drained ZERO batches while a peer drained some:
                    # synthesize the padding from the schema/placement layout
                    # so this host still steps in lockstep with its peers
                    # instead of raising after the allgather (which would hang
                    # the pod mid-collective - the exact failure drain()
                    # exists to prevent)
                    for name, (shape, sharding, dtype) in synthesized.items():
                        if sharding is not None:
                            pad[name] = _zero_array(shape, sharding, dtype)
                        else:
                            pad[name] = np.zeros(shape, dtype)  # host field
                # stack mode: per-step counts, all zero (shape matches the
                # real units' '_valid_rows' array so consumer code is uniform)
                pad["_valid_rows"] = (np.zeros(self._stack, np.int64)
                                      if self._stack > 1 else 0)
                yield pad
        return _aligned()

    def _pad_batch_layout(self) -> Dict:
        """field -> (global shape, sharding | None, dtype) for synthesizing
        drain-alignment pad batches when this host delivered no batch to use
        as a template.  The placement cache (populated per emitted batch) wins
        because it reflects ``transform_fn`` output shapes; otherwise shapes
        come from the schema (fixed shapes, single-bucket pad targets, device
        decode geometry)."""
        layout: Dict[str, Tuple] = {}
        # when batches WERE emitted, their staged field set is the pytree the
        # peers' steps expect - a transform_fn may have added or dropped
        # fields relative to self._fields
        staged = (list(self._emitted_layout)
                  + [n for n in self._device_decode if n in self._fields]
                  if self._emitted_layout
                  else list(self._fields) + list(self._extent_field))
        if self._valid_mask is not None and self._valid_mask not in staged:
            staged.append(self._valid_mask)
        for name in staged:
            field = self._schema[name] if name in self._schema else None
            emitted = self._emitted_layout.get(name)
            if emitted is not None:
                # last-emitted layout, not the schema's: a transform_fn may
                # have changed the dtype (uint8 image -> float32) and
                # multi-bucket pad_shapes make the trailing shape per-batch;
                # peers pad from their LAST drained batch, so mirroring the
                # last emitted batch here is the same semantics
                trailing, dtype = emitted
                sharding, _ = self._placement_cache[(name, trailing)]
            elif name == self._valid_mask:
                trailing = ()
                sharding = NamedSharding(self._mesh, self._delivery_spec(name))
                dtype = np.float32
            elif name in self._extent_field:
                trailing = (2,)
                sharding = NamedSharding(self._mesh, self._delivery_spec(name))
                dtype = np.int32
            elif name in self._device_decode:
                # mixed fields may declare a variable shape; their static
                # delivery shape is the largest canvas, not the schema shape
                trailing = (self._mixed_target(name)
                            if name in self._mixed_decode
                            else tuple(field.shape))
                sharding = NamedSharding(self._mesh, self._delivery_spec(name))
                dtype = np.uint8
            else:
                if self._transform_fn is not None:
                    raise PetastormTpuError(
                        "drain() alignment on a zero-batch host cannot derive"
                        f" the padded shape of field {name!r}: a transform_fn"
                        " is set and no batch was ever emitted here to learn"
                        " its output shape - checkpoint at a step boundary"
                        " instead")
                buckets = self._pad_shapes.get(name)
                if buckets and len(buckets) > 1:
                    raise PetastormTpuError(
                        "drain() alignment on a zero-batch host cannot pick a"
                        f" pad bucket for field {name!r} (multi-bucket"
                        " pad_shapes): peers pad from their own last batch's"
                        " bucket, so a guess here could silently diverge the"
                        " pod's global shapes - checkpoint at a step boundary"
                        " instead")
                trailing = tuple(buckets[0]) if buckets else tuple(field.shape)
                sharding = NamedSharding(self._mesh, self._delivery_spec(name))
                dtype = jax_feed_dtype(field.dtype, keep_wide=self._keep_wide)
            layout[name] = (self._delivery_global(trailing), sharding, dtype)
        lead = (self._stack,) if self._stack > 1 else ()
        for name in self._host_fields:
            field = self._schema[name]
            shape = tuple(d if d is not None else 0 for d in field.shape)
            host_dtype = field.dtype if field.dtype.kind not in "USOMm" else object
            layout[name] = (lead + (self._local_rows,) + shape, None, host_dtype)
        return layout

    def state_dict(self) -> Dict:
        """Data-position cursor to pair with a training checkpoint.

        ``reader`` is the underlying work-item cursor (pass back via
        ``make_reader(..., resume_from=...)`` / ``resume_reader_kwargs``);
        ``delivered_batches`` counts device UNITS handed to the consumer -
        single batches, or whole ``(K, batch, ...)`` stacks in stack mode
        (``stack_batches=K``), so cursor granularity follows delivery
        granularity.
        Mid-epoch the reader cursor can run ahead of deliveries by the
        in-flight window - both producer-stage queues (2x ``prefetch``) plus
        ALL ``device_shuffle_capacity`` resident batches - so keep buffers
        small (or zero) when tight resume matters, or use ``drain()`` first
        for an exact cursor (see petastorm_tpu.jax.checkpoint module docs).
        """
        if not hasattr(self._reader, "state_dict"):
            raise PetastormTpuError(
                f"Reader {type(self._reader).__name__} does not support"
                " state_dict(); checkpoint/resume needs a petastorm_tpu Reader")
        return {"reader": self._reader.state_dict(),
                "delivered_batches": self._delivered_batches,
                "global_batch": self._global_batch,
                "stack_batches": self._stack}

    # -- lifecycle ------------------------------------------------------------

    def _stop_trace(self) -> None:
        if self._tracing:
            self._tracing = False
            try:
                jax.profiler.stop_trace()
            except RuntimeError as exc:  # no trace running (stopped elsewhere)
                logger.debug("stop_trace: %s", exc)

    def stop(self) -> None:
        """Stop the producer pipeline and the underlying reader."""
        self._stop_event.set()
        self._reader.stop()
        self._stop_trace()

    def join(self) -> None:
        """Wait for the producer threads and the reader to exit (after stop()).

        Each producer thread gets a bounded join; one that fails to quiesce
        (wedged in a transform_fn, a device transfer that never completes)
        is NOT silently ignored: a WARNING names the thread and its pipeline
        stage, and the failure is recorded in
        ``diagnostics['unquiesced_threads']``.  The threads are daemonic, so
        an abandoned one cannot block process exit.
        """
        if self._started:
            for t, stage in ((self._thread, "host-assemble"),
                             (self._transfer_thread, "device-transfer")):
                t.join(timeout=10)
                if t.is_alive():
                    entry = {"thread": t.name, "stage": stage}
                    if entry not in self._unquiesced:
                        self._unquiesced.append(entry)
                    logger.warning(
                        "Loader producer thread %s (stage %s) failed to"
                        " quiesce within 10s of stop(); abandoning the daemon"
                        " thread. queue depths: host=%d out=%d", t.name,
                        stage, self._host_q.qsize(), self._out.qsize())
        self._reader.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        self.join()


def make_jax_loader(dataset_url: str,
                    batch_size: int,
                    mesh: Optional[Mesh] = None,
                    shardings=None,
                    reader_factory=None,
                    shard_by_process: bool = True,
                    **kwargs) -> JaxDataLoader:
    """One-call path: dataset URL -> sharded reader -> JaxDataLoader.

    Shard assignment defaults to the JAX process topology
    (``jax.process_index/process_count``) - the TPU-native replacement for the
    reference's externally-supplied ``cur_shard`` + env-var rank sniffing.

    Reader kwargs (predicate, num_epochs, shuffle_seed, ...) and loader kwargs
    (shuffling_queue_capacity, pad_shapes, ...) are split automatically.
    """
    import inspect

    from petastorm_tpu.reader import make_batch_reader

    loader_params = set(inspect.signature(JaxDataLoader.__init__).parameters) - {
        "self", "reader", "batch_size", "mesh", "shardings"}
    loader_kwargs = {k: kwargs.pop(k) for k in list(kwargs) if k in loader_params}
    if "telemetry" in loader_kwargs:
        # one recorder observes the whole pipeline: the reader gets it too
        # (the loader would otherwise inherit the reader's default recorder)
        kwargs["telemetry"] = loader_kwargs["telemetry"]
    if "schema_fields" not in kwargs:
        # don't read+decode columns the loader would only throw away
        wanted = list(loader_kwargs.get("fields") or [])
        wanted += list(loader_kwargs.get("host_fields") or [])
        if wanted:
            kwargs["schema_fields"] = wanted

    if shard_by_process and "cur_shard" not in kwargs:
        cur, count = jax.process_index(), jax.process_count()
        if count > 1:
            kwargs["cur_shard"], kwargs["shard_count"] = cur, count
    factory = reader_factory or make_batch_reader
    reader = factory(dataset_url, **kwargs)
    try:
        return JaxDataLoader(reader, batch_size, mesh=mesh, shardings=shardings,
                             **loader_kwargs)
    except BaseException:
        # the loader never came to own the reader: shut it down, or its
        # executor threads/ventilator would poll forever
        reader.stop()
        reader.join()
        raise


def _pad_rows(col: np.ndarray, rows: int, fill=0) -> np.ndarray:
    """Pad a column's leading axis to ``rows`` with ``fill`` (the one shared
    tail-pad policy: zeros for data/coefficient planes, ones for quant
    tables)."""
    if len(col) >= rows:
        return col
    shape = (rows - len(col),) + col.shape[1:]
    filler = np.zeros(shape, col.dtype) if fill == 0 else np.full(
        shape, fill, col.dtype)
    return np.concatenate([col, filler])


def _host_filler(tmpl: np.ndarray) -> np.ndarray:
    """A zero-information array shaped like ``tmpl`` for missing host-side
    steps/rows (object cells fill with None, numeric with zeros)."""
    if tmpl.dtype == object:
        return np.full(tmpl.shape, None, dtype=object)
    return np.zeros_like(tmpl)


def _pad_host_col(col: np.ndarray, rows: int) -> np.ndarray:
    """Pad a host-side column to ``rows`` entries for stack assembly (object
    cells pad with None, numeric with zeros - same policy as the step filler
    ``_host_filler``)."""
    col = np.asarray(col)
    if len(col) >= rows:
        return col
    if col.dtype == object:
        filler = np.full((rows - len(col),) + col.shape[1:], None,
                         dtype=object)
    else:
        filler = np.zeros((rows - len(col),) + col.shape[1:], col.dtype)
    return np.concatenate([col, filler])


def _normalize_buckets(name: str, spec) -> list:
    """pad_shapes entry -> non-empty list of equal-rank bucket tuples, sorted
    by total size (so 'smallest fitting bucket' is a linear scan)."""
    buckets = [tuple(spec)] if spec and not isinstance(spec[0], (list, tuple)) \
        else [tuple(b) for b in spec]
    if not buckets:
        raise PetastormTpuError(f"pad_shapes[{name!r}] is empty")
    ranks = {len(b) for b in buckets}
    if len(ranks) != 1:
        raise PetastormTpuError(
            f"pad_shapes[{name!r}] buckets must share one rank, got {buckets}")
    return sorted(buckets, key=lambda b: (int(np.prod(b)), b))


def _pick_bucket(col: np.ndarray, buckets: list) -> Tuple[int, ...]:
    """Smallest bucket that fits every row of this batch (largest otherwise -
    rows are then clipped, same semantics as a single too-small target)."""
    if len(buckets) == 1:
        return buckets[0]
    if col.dtype != object:
        need = col.shape[1:]
    else:
        shapes = np.array([np.asarray(r).shape for r in col])
        need = tuple(shapes.max(axis=0)) if len(shapes) else buckets[0]
    for b in buckets:
        if len(b) == len(need) and all(t >= n for t, n in zip(b, need)):
            return b
    return buckets[-1]


def _pad_to(col: np.ndarray, target: Tuple[int, ...], pad_value, dtype) -> np.ndarray:
    """Pad/truncate each row to ``target`` shape (pad-to-bucket for XLA)."""
    n = len(col)
    target = tuple(target)
    if col.dtype != object:
        # already stacked (all rows same shape): one vectorized copy
        if col.shape[1:] == target:
            return col
        if col.ndim - 1 != len(target):
            raise PetastormTpuError(
                f"pad_shapes rank mismatch: rows have shape {col.shape[1:]},"
                f" target {target}")
        out = np.full((n,) + target, pad_value, dtype=dtype)
        clipped = tuple(slice(0, min(a, b)) for a, b in zip(col.shape[1:], target))
        out[(slice(None),) + clipped] = col[(slice(None),) + clipped]
        return out
    out = np.full((n,) + target, pad_value, dtype=dtype)
    for i in range(n):
        row = np.asarray(col[i])
        if row.ndim != len(target):
            raise PetastormTpuError(
                f"pad_shapes rank mismatch: row has shape {row.shape}, target"
                f" {target}")
        clipped = tuple(slice(0, min(a, b)) for a, b in zip(row.shape, target))
        out[(i,) + clipped] = row[clipped]
    return out
