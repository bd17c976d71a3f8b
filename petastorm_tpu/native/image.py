"""ctypes binding for the native batched PNG/JPEG decoder (image_decode.cpp).

``decode_column_native`` decodes a whole ``pyarrow`` binary column of encoded
image streams into one preallocated contiguous uint8 array in a single
GIL-released C call, reading the streams zero-copy straight out of the arrow
data buffer (no ``to_pylist``, no per-cell Python objects).

Replaces the reference's per-cell ``cv2.imdecode`` loop
(petastorm/codecs.py:92-101) on the hot path; codecs.CompressedImageCodec falls
back to cv2/PIL when the native library or the input shape doesn't qualify.
"""

from __future__ import annotations

import ctypes
import logging
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

#: per-THREAD decode statistics (monotonic).  The worker plane folds deltas
#: into telemetry counters (``decode.batch_*``) after each rowgroup, so
#: callers here stay telemetry-free.  Thread-local: a pool worker folding
#: the delta around its own decode must not absorb a sibling thread's
#: concurrent increments (that double-counts the shared registry).
_STATS_TLS = threading.local()
_STAT_KEYS = ("batch_calls", "batch_images", "roi_calls", "roi_images",
              "coef_batch_calls", "coef_batch_images")


def _tls_stats() -> dict:
    stats = getattr(_STATS_TLS, "stats", None)
    if stats is None:
        stats = _STATS_TLS.stats = {k: 0 for k in _STAT_KEYS}
    return stats


def _count(**deltas) -> None:
    stats = _tls_stats()
    for name, d in deltas.items():
        stats[name] += d


def decode_stats() -> dict:
    """Snapshot of THIS thread's cumulative native-decode counters."""
    return dict(_tls_stats())


_warned_unavailable = False

#: the one-command build this module falls back from when missing
BUILD_COMMAND = ("python -c \"from petastorm_tpu.native import build;"
                 " print(build.build('image_decode'))\"")


def _warn_unavailable() -> None:
    """One-time WARNING when a decode hot path falls back to per-cell
    cv2/PIL because the native library is absent - previously a silent
    ~N-times-slower degradation."""
    global _warned_unavailable
    if _warned_unavailable:
        return
    _warned_unavailable = True
    logger.warning(
        "native image decode library is unavailable - image columns fall"
        " back to the per-cell cv2/PIL decode path (GIL-bound, several"
        " times slower on image-heavy reads). Build it once with: %s",
        BUILD_COMMAND)


def _configure(lib: ctypes.CDLL) -> None:
    lib.pst_decode_image_batch.restype = ctypes.c_int
    lib.pst_decode_image_batch.argtypes = [
        ctypes.c_void_p,  # const uint8_t* const* srcs (uint64 array)
        ctypes.c_void_p,  # const uint64_t* lens
        ctypes.c_int,     # n
        ctypes.c_void_p,  # uint8_t* out
        ctypes.c_uint64,  # stride
        ctypes.c_int, ctypes.c_int, ctypes.c_int,  # h, w, c
        ctypes.c_int,     # nthreads
    ]
    lib.pst_decode_image.restype = ctypes.c_int
    lib.pst_decode_image.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ]
    lib.pst_decode_image_batch_roi.restype = ctypes.c_int
    lib.pst_decode_image_batch_roi.argtypes = [
        ctypes.c_void_p,  # srcs
        ctypes.c_void_p,  # lens
        ctypes.c_int,     # n
        ctypes.c_void_p,  # out
        ctypes.c_uint64,  # stride
        ctypes.c_int, ctypes.c_int, ctypes.c_int,  # full h, w, c
        ctypes.c_void_p,  # crop_ys (int32)
        ctypes.c_void_p,  # crop_xs (int32)
        ctypes.c_int, ctypes.c_int,  # crop_h, crop_w
        ctypes.c_int,     # nthreads
    ]
    lib.pst_jpeg_coef_layout.restype = ctypes.c_int
    lib.pst_jpeg_coef_layout.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_void_p,
    ]
    lib.pst_jpeg_read_coefs.restype = ctypes.c_int
    lib.pst_jpeg_read_coefs.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.pst_jpeg_coef_batch.restype = ctypes.c_int
    lib.pst_jpeg_coef_batch.argtypes = [
        ctypes.c_void_p,  # const uint8_t* const* srcs (uint64 array)
        ctypes.c_void_p,  # const uint64_t* lens
        ctypes.c_int,     # n
        ctypes.c_void_p,  # int16_t* const* outs
        ctypes.c_void_p,  # const uint64_t* plane_strides
        ctypes.c_void_p,  # uint16_t* qtabs
        ctypes.c_void_p,  # const int32_t* meta
        ctypes.c_int,     # nthreads
    ]


def _load() -> Optional[ctypes.CDLL]:
    from petastorm_tpu.native import build

    return build.load_library("image_decode", _configure)


def available() -> bool:
    return _load() is not None


def available_or_warn() -> bool:
    """Like :func:`available`, but a miss emits the one-time fallback WARNING
    naming the build command - for decode hot paths, where silence hid a
    several-times-slower degradation (use plain ``available()`` in
    validation/capability checks)."""
    if _load() is not None:
        return True
    _warn_unavailable()
    return False


def _column_pointers(column) -> Optional[tuple]:
    """(ptrs uint64 array, lens uint64 array) for a binary arrow array, zero-copy."""
    import pyarrow as pa

    if column.null_count:
        return None
    typ = column.type
    if typ == pa.binary():
        off_dtype = np.int32
    elif typ == pa.large_binary():
        off_dtype = np.int64
    else:
        return None
    buffers = column.buffers()  # [validity, offsets, data]
    if len(buffers) != 3 or buffers[1] is None or buffers[2] is None:
        return None
    n = len(column)
    offsets = np.frombuffer(
        buffers[1], dtype=off_dtype, count=n + 1,
        offset=column.offset * np.dtype(off_dtype).itemsize).astype(np.uint64)
    ptrs = np.uint64(buffers[2].address) + offsets[:-1]
    lens = offsets[1:] - offsets[:-1]
    return ptrs, lens


def decode_column_native(column, out: np.ndarray, nthreads: int = 1,
                         roi: Optional[tuple] = None,
                         full_shape: Optional[tuple] = None) -> bool:
    """Decode a binary arrow column of PNG/JPEG streams into ``out``.

    ``out`` must be contiguous uint8 of shape (n, h, w, c) or (n, h, w).
    ``nthreads > 1`` fans the batch out over the library's internal thread
    pool (the whole call releases the GIL either way).

    ROI (partial) decode: with ``roi=(crop_ys, crop_xs)`` (per-image int
    offsets, scalars broadcast) and ``full_shape=(H, W)`` (the stored image
    geometry), each image decodes only the ``out``-shaped window anchored at
    its offset - rows below the crop are never entropy-decoded, and the
    result is byte-identical to slicing a full decode (crops need not be
    8x8-block aligned).

    Returns False (without touching ``out``'s validity) when the native path
    doesn't apply; raises on an actual decode failure.
    """
    lib = _load()
    if lib is None:
        _warn_unavailable()
        return False
    if out.dtype != np.uint8 or not out.flags.c_contiguous:
        return False
    if out.ndim == 3:
        n, h, w = out.shape
        c = 1
    elif out.ndim == 4:
        n, h, w, c = out.shape
    else:
        return False
    if c not in (1, 3, 4):
        return False
    pointers = _column_pointers(column)
    if pointers is None:
        return False
    ptrs, lens = pointers
    if len(ptrs) != n:
        return False
    if n == 0:
        return True
    if roi is not None:
        full_h, full_w = full_shape
        ys = np.ascontiguousarray(
            np.broadcast_to(np.asarray(roi[0], dtype=np.int32), (n,)))
        xs = np.ascontiguousarray(
            np.broadcast_to(np.asarray(roi[1], dtype=np.int32), (n,)))
        rc = lib.pst_decode_image_batch_roi(
            ptrs.ctypes.data, lens.ctypes.data, n,
            out.ctypes.data, np.uint64(out.strides[0]), full_h, full_w, c,
            ys.ctypes.data, xs.ctypes.data, h, w, nthreads)
        if rc == 0:
            _count(roi_calls=1, roi_images=n)
    else:
        rc = lib.pst_decode_image_batch(
            ptrs.ctypes.data, lens.ctypes.data, n,
            out.ctypes.data, np.uint64(out.strides[0]), h, w, c, nthreads)
        if rc == 0:
            _count(batch_calls=1, batch_images=n)
    if rc != 0:
        from petastorm_tpu.errors import CodecError

        raise CodecError(
            f"native image decode failed at cell {rc - 1} (expected shape "
            f"({h}, {w}, {c}) uint8"
            + (f" cropped from {full_shape}" if roi is not None else "")
            + "; corrupt stream, crop outside image, or shape mismatch)")
    return True


# -- hybrid JPEG decode: host entropy half (see ops/jpeg.py for the TPU half) --

_JPEG_MAX_COMPS = 4
_JPEG_META_LEN = 3 + 4 * _JPEG_MAX_COMPS


class JpegCoefLayout:
    """Geometry of one JPEG's coefficient planes (all values in 8x8 blocks)."""

    __slots__ = ("width", "height", "components")

    def __init__(self, width: int, height: int, components):
        self.width = width
        self.height = height
        #: per component: (h_samp, v_samp, blocks_w, blocks_h)
        self.components = components

    def __eq__(self, other):
        return (isinstance(other, JpegCoefLayout)
                and (self.width, self.height, self.components)
                == (other.width, other.height, other.components))

    def __repr__(self):
        return (f"JpegCoefLayout({self.width}x{self.height},"
                f" comps={self.components})")


def jpeg_coef_layout(buf: bytes) -> Optional["JpegCoefLayout"]:
    """Parse a JPEG header into its coefficient-plane geometry (no entropy
    decode); None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    meta = np.zeros(_JPEG_META_LEN, dtype=np.int32)
    rc = lib.pst_jpeg_coef_layout(bytes(buf), len(buf), meta.ctypes.data)
    if rc != 0:
        from petastorm_tpu.errors import CodecError

        raise CodecError(f"not a decodable JPEG (rc={rc})")
    return _layout_from_meta(meta)


def _layout_from_meta(meta) -> "JpegCoefLayout":
    """Inverse of ``_layout_meta``: int32 meta vector -> JpegCoefLayout."""
    ncomp = int(meta[0])
    comps = tuple(tuple(int(v) for v in meta[3 + 4 * c: 7 + 4 * c])
                  for c in range(ncomp))
    return JpegCoefLayout(int(meta[1]), int(meta[2]), comps)


def read_jpeg_coefficients(buf: bytes, layout: Optional[JpegCoefLayout] = None):
    """Entropy-decode one JPEG into quantized DCT coefficient planes.

    Returns ``(planes, qtabs, layout)``: ``planes[c]`` is int16
    (blocks_h, blocks_w, 64) in natural order, ``qtabs`` is uint16 (ncomp, 64).
    The FLOP-heavy rest of the decode (dequant + IDCT + upsample + color)
    belongs on the TPU: ``petastorm_tpu.ops.jpeg.decode_coefficients``.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native image library unavailable")
    if layout is None:
        layout = jpeg_coef_layout(buf)
    planes = [np.empty((bh, bw, 64), dtype=np.int16)
              for (_, _, bw, bh) in layout.components]
    qtabs = np.empty((len(layout.components), 64), dtype=np.uint16)
    outs = (ctypes.c_void_p * len(planes))(
        *[p.ctypes.data for p in planes])
    rc = lib.pst_jpeg_read_coefs(bytes(buf), len(buf),
                                 ctypes.cast(outs, ctypes.c_void_p),
                                 qtabs.ctypes.data)
    if rc != 0:
        from petastorm_tpu.errors import CodecError

        raise CodecError(f"JPEG coefficient read failed (rc={rc})")
    return planes, qtabs, layout


def _layout_meta(layout: JpegCoefLayout) -> np.ndarray:
    meta = np.zeros(_JPEG_META_LEN, dtype=np.int32)
    meta[0] = len(layout.components)
    meta[1] = layout.width
    meta[2] = layout.height
    for c, comp in enumerate(layout.components):
        meta[3 + 4 * c: 7 + 4 * c] = comp
    return meta


#: separator for derived coefficient-plane column names: a device-decode
#: field ``img`` travels the pipeline as ``img#p0..img#p{ncomp-1}`` (int16
#: block planes), ``img#q`` (uint16 quant tables) and ``img#m`` (int32 layout
#: meta, identical per row).  Fixed-shape numpy columns ride the shuffle
#: buffers, the rebatcher and the shm arena like any other column - the
#: entropy half of the decode runs in pool workers, not the loader thread.
COEF_COLUMN_SEP = "#"


def pack_coef_columns(name: str, column, field=None, nthreads: int = 1) -> dict:
    """Entropy-decode a jpeg column into its derived plane columns.

    Worker side of the device-decode path: one GIL-released C call per
    rowgroup; the output dict's arrays are all fixed-shape per geometry, so
    downstream batching/shuffling/shm transport treat them as ordinary
    columns.  ``field`` (optional Schema field) enables the early
    schema-shape check.  Raises CodecError with migration guidance when the
    dataset's jpeg geometry is not uniform - the device path compiles the
    on-chip decode once per geometry, so mixed-subsampling datasets belong
    on decode_placement='host'.
    """
    from petastorm_tpu.errors import CodecError

    try:
        planes, qtabs, layout = read_jpeg_coefficients_column(
            column, nthreads=nthreads)
    except CodecError as exc:
        raise CodecError(
            f"decode_placement='device' field {name!r}:"
            f" {_diagnose_coef_failure(column, exc)}") from exc
    if field is not None and (layout.height, layout.width) != tuple(field.shape[:2]):
        raise CodecError(
            f"field {name!r}: stored jpeg is {layout.height}x{layout.width},"
            f" schema says {tuple(field.shape[:2])}")
    n = len(qtabs)
    out = {f"{name}{COEF_COLUMN_SEP}p{c}": p for c, p in enumerate(planes)}
    out[f"{name}{COEF_COLUMN_SEP}q"] = qtabs
    out[f"{name}{COEF_COLUMN_SEP}m"] = np.broadcast_to(
        _layout_meta(layout), (n, _JPEG_META_LEN))
    return out


_MIXED_GEOMETRY_GUIDANCE = (
    "decode_placement='device' requires every stored jpeg to share one"
    " geometry and subsampling (XLA compiles the on-chip decode per geometry)."
    " Use decode_placement='device-mixed' (on-chip decode in fixed"
    " canvases), re-encode uniformly (petastorm-tpu-copy-dataset --jpeg-quality),"
    " or use decode_placement='host'")


def _diagnose_coef_failure(column, exc) -> str:
    """Turn a batch coefficient-read failure into actionable guidance:
    distinguish a corrupt cell (host decode would fail too) from mixed
    geometry (host decode would work - point at decode_placement='host')."""
    from petastorm_tpu.errors import CodecError

    cells = column if isinstance(column, (list, tuple)) else column.to_pylist()
    first = None
    try:
        for i, cell in enumerate(cells):
            try:
                lay = jpeg_coef_layout(bytes(cell))
            except CodecError:
                return (f"cell {i} is not a decodable jpeg (corrupt or"
                        f" truncated stream): {exc}")
            if first is None:
                first = lay
            elif lay != first:
                return (f"cell {i} has geometry {lay} but cell 0 has {first}:"
                        f" {_MIXED_GEOMETRY_GUIDANCE}")
    except Exception:  # noqa: BLE001 - diagnosis is best-effort
        pass
    # headers parse and agree: entropy-level corruption, or the simulated
    # failure injected by tests
    return f"{exc}. If the dataset mixes jpeg geometries: {_MIXED_GEOMETRY_GUIDANCE}."


#: suffix of the MIXED-geometry wire column: one object cell per row holding
#: ``(per-component plane tuple, qtab (ncomp, 64), layout-meta int32 vector)``.
#: Object columns ride batching/shuffle; the shm transport pickles them
#: (native/transport.py object fallback) - slower than the fixed-shape plane
#: columns, which stay the uniform-geometry fast path.
MIXED_CELL_SUFFIX = "x"


def pack_coef_columns_mixed(name: str, column, field=None,
                            nthreads: int = 1) -> dict:
    """Entropy-decode a jpeg column of MIXED geometries into one object column.

    Worker side of ``decode_placement='device-mixed'``: rows are grouped by
    coefficient-plane geometry (header parse only), each group entropy-decodes
    through the batched GIL-released C call, and every row becomes one object
    cell ``(planes, qtab, meta)``.  The jax loader pads the assembled
    batch's planes into fixed canvases and runs the on-chip half once per
    canvas group (petastorm_tpu/ops/jpeg.py ``finish_canvas``), so XLA
    compiles are bounded by chroma layouts x canvases x group sizes.

    A fixed-shape schema field must match every stored geometry; declare
    wildcard dims (e.g. ``(None, None, 3)``) for genuinely mixed datasets.
    """
    from petastorm_tpu.errors import CodecError

    cells = (list(column) if isinstance(column, (list, tuple))
             else column.to_pylist())
    if not cells:
        raise CodecError(f"field {name!r}: empty jpeg column")
    groups: dict = {}
    for i, buf in enumerate(cells):
        try:
            layout = jpeg_coef_layout(bytes(buf))
        except CodecError as exc:
            raise CodecError(
                f"decode_placement='device-mixed' field {name!r}: cell {i} is"
                f" not a decodable jpeg (corrupt or truncated stream): {exc}"
            ) from exc
        if field is not None and field.is_fixed_shape and (
                layout.height, layout.width) != tuple(field.shape[:2]):
            raise CodecError(
                f"field {name!r}: stored jpeg is {layout.height}x{layout.width},"
                f" schema says {tuple(field.shape[:2])}; declare wildcard dims"
                " (None, None, ...) for mixed-geometry datasets")
        groups.setdefault(_layout_meta(layout).tobytes(), []).append(i)
    out = np.empty(len(cells), dtype=object)
    for key, idxs in groups.items():
        planes, qtabs, layout = read_jpeg_coefficients_column(
            [cells[i] for i in idxs], nthreads=nthreads)
        meta = np.frombuffer(key, dtype=np.int32)
        for j, i in enumerate(idxs):
            out[i] = (tuple(p[j] for p in planes), qtabs[j], meta)
    return {f"{name}{COEF_COLUMN_SEP}{MIXED_CELL_SUFFIX}": out}


def unpack_coef_columns(name: str, columns: dict):
    """Consumer side: derived columns of one assembled batch ->
    ``(planes, qtabs, layout)``.  Verifies the rows share one geometry -
    batch assembly may have concatenated different rowgroups."""
    from petastorm_tpu.errors import CodecError

    meta_col = columns[f"{name}{COEF_COLUMN_SEP}m"]
    if len(meta_col) == 0:
        raise CodecError(f"field {name!r}: empty coefficient batch")
    if not (meta_col == meta_col[0]).all():
        raise CodecError(
            f"field {name!r}: jpeg geometry changes between rowgroups of"
            " this dataset; the device decode path needs one uniform"
            " geometry - use decode_placement='host'.")
    layout = _layout_from_meta(meta_col[0])
    ncomp = len(layout.components)
    planes = [columns[f"{name}{COEF_COLUMN_SEP}p{c}"] for c in range(ncomp)]
    qtabs = columns[f"{name}{COEF_COLUMN_SEP}q"]
    return planes, qtabs, layout


def read_jpeg_coefficients_column(column, nthreads: int = 1):
    """Entropy-decode a column of same-geometry JPEGs into stacked planes.

    One GIL-released C call over the whole batch, reading the streams
    zero-copy out of the arrow buffer when ``column`` is an arrow binary
    array.  Returns ``(planes, qtabs, layout)`` where ``planes[c]`` is int16
    (n, blocks_h, blocks_w, 64) and ``qtabs`` is uint16 (n, ncomp, 64) -
    ready to ship to the device as one contiguous transfer per component.
    Raises CodecError when geometries differ (caller falls back to per-image
    host decode).
    """
    from petastorm_tpu.errors import CodecError

    lib = _load()
    if lib is None:
        raise RuntimeError("native image library unavailable")
    if isinstance(column, (list, tuple)):
        cells = [np.frombuffer(b, dtype=np.uint8) for b in column]
        ptrs = np.array([c.ctypes.data for c in cells], dtype=np.uint64)
        lens = np.array([len(c) for c in cells], dtype=np.uint64)
        first = column[0] if column else b""
    else:
        pointers = _column_pointers(column)
        if pointers is None:  # chunked/offset edge cases: fall back to copies
            return read_jpeg_coefficients_column(column.to_pylist(),
                                                 nthreads=nthreads)
        ptrs, lens = pointers
        first = column[0].as_py() if len(column) else b""
    n = len(ptrs)
    if n == 0:
        raise CodecError("empty column")
    layout = jpeg_coef_layout(first)
    ncomp = len(layout.components)
    planes = [np.empty((n, bh, bw, 64), dtype=np.int16)
              for (_, _, bw, bh) in layout.components]
    qtabs = np.empty((n, ncomp, 64), dtype=np.uint16)
    outs = (ctypes.c_void_p * ncomp)(*[p.ctypes.data for p in planes])
    strides = np.array([p.strides[0] // 2 for p in planes], dtype=np.uint64)
    meta = _layout_meta(layout)
    rc = lib.pst_jpeg_coef_batch(
        ptrs.ctypes.data, lens.ctypes.data, n,
        ctypes.cast(outs, ctypes.c_void_p), strides.ctypes.data,
        qtabs.ctypes.data, meta.ctypes.data, nthreads)
    if rc != 0:
        raise CodecError(
            f"JPEG coefficient batch failed at cell {rc - 1} (corrupt stream"
            f" or geometry differs from {layout})")
    _count(coef_batch_calls=1, coef_batch_images=n)
    return planes, qtabs, layout
