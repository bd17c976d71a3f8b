"""'device-mixed' finish in fixed canvases, the per-image extents it
delivers, and the random-resized crop drawn inside them.

ILSVRC-2012 stores hundreds of image geometries.  The loader pads each
image's coefficient planes into the smallest of a few canvases and the chip
finishes groups of images one canvas at a time, so the programs it compiles
are counted in canvases, not in geometries.  These tests hold the canvas
finish to the per-geometry finish (``ops.jpeg.decode_coefficients``), the
delivered extents to the stored geometry, and the crop to a plain float32
resample of the unpadded image.
"""

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from petastorm_tpu.native import image as native_image  # noqa: E402

if not native_image.available():
    pytest.skip("native image library unavailable", allow_module_level=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from petastorm_tpu import ops  # noqa: E402
from petastorm_tpu import telemetry as T  # noqa: E402
from petastorm_tpu.codecs import CompressedImageCodec  # noqa: E402
from petastorm_tpu.etl.writer import write_dataset  # noqa: E402
from petastorm_tpu.jax import JaxDataLoader  # noqa: E402
from petastorm_tpu.ops.jpeg import decode_coefficients, finish_canvas  # noqa: E402
from petastorm_tpu.reader import make_batch_reader  # noqa: E402
from petastorm_tpu.schema import Field, Schema  # noqa: E402

from tests.test_jpeg_hybrid import _cv2_decode, _encode  # noqa: E402

SAMPLING = {"420": None,
            "444": getattr(cv2, "IMWRITE_JPEG_SAMPLING_FACTOR_444", None)}
#: canvases of the loader tests, smallest first; the last is delivered
CANVASES = [(32, 48, 3), (48, 32, 3), (48, 48, 3), (64, 64, 3)]


def _textured(h, w, seed):
    """A smooth field with edges and fine noise: every pixel matters."""
    rng = np.random.default_rng(seed)
    base = cv2.resize(rng.uniform(0, 255, (3, 3, 3)).astype(np.float32),
                      (w, h), interpolation=cv2.INTER_CUBIC)
    cv2.circle(base, (w // 2, h // 3), max(min(h, w) // 4, 1),
               tuple(float(c) for c in rng.uniform(0, 255, 3)), -1)
    base += rng.normal(0, 12, (h, w, 3)).astype(np.float32)
    return np.clip(base, 0, 255).astype(np.uint8)


def _geometries(seed, n, hi=64):
    """``n`` distinct (h, w), odd and even, most not whole MCUs."""
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        g = (int(rng.integers(9, hi + 1)), int(rng.integers(9, hi + 1)))
        if g not in out:
            out.append(g)
    return out


def _chroma(name):
    if SAMPLING[name] is None and name != "420":
        pytest.skip("cv2 build lacks sampling-factor control")
    return SAMPLING[name]


def _own_finish(buf):
    """The per-geometry finish of one stored jpeg: what 'device' decode
    gives at the image's own size."""
    planes, qtabs, lay = native_image.read_jpeg_coefficients(buf)
    sampling = tuple((h, v) for (h, v, _, _) in lay.components)
    return np.asarray(decode_coefficients(
        tuple(jnp.asarray(p[None]) for p in planes), jnp.asarray(qtabs[None]),
        image_size=(lay.height, lay.width), sampling=sampling))[0]


def _assert_equal_inside(got, want, where):
    """Equal, up to float rounding: the two compiled programs round a few
    values that lie within an ulp of .5 apart (about one in 10^5)."""
    diff = np.abs(got.astype(np.int32) - want.astype(np.int32))
    assert diff.max() <= 1, where
    assert (diff > 0).mean() < 1e-3, where


@pytest.mark.parametrize("chroma", ["420", "444"])
def test_canvas_finish_equals_per_geometry_finish(chroma):
    """Over 14 distinct geometries, odd and not MCU-aligned at the right
    and bottom edges, packed in groups into two canvases (with dropped
    padding rows), the canvas finish gives each image's own finish inside
    its extent and 0 outside."""
    sampling_flag = _chroma(chroma)
    geoms = _geometries(1 if chroma == "420" else 2, 14)
    bufs = [_encode(_textured(h, w, i), quality=90, sampling=sampling_flag)
            for i, (h, w) in enumerate(geoms)]
    from petastorm_tpu.jax.loader import _CanvasGroup

    cells = []
    for buf in bufs:
        planes, qtab, lay = native_image.read_jpeg_coefficients(buf)
        cells.append((tuple(planes), qtab, None))
    sampling = tuple((h, v) for (h, v, _, _)
                     in native_image.jpeg_coef_layout(bufs[0]).components)
    extents = np.asarray(geoms, np.int32)
    out = jnp.zeros((len(geoms), 64, 80, 3), jnp.uint8)
    for canvas, members in (((48, 64), [i for i, (h, w) in enumerate(geoms)
                                        if h <= 48 and w <= 64]),
                            ((64, 80), [i for i, (h, w) in enumerate(geoms)
                                        if h > 48 or w > 64])):
        for a in range(0, len(members), 4):
            g = _CanvasGroup.pack(cells, members[a:a + 4], extents, canvas,
                                  sampling, 4)
            rows = np.where(g.rows < 0, len(geoms), g.rows)
            out = finish_canvas(out, tuple(jnp.asarray(p) for p in g.planes),
                                jnp.asarray(g.qtabs), jnp.asarray(g.extents),
                                jnp.asarray(rows.astype(np.int32)),
                                canvas_hw=canvas, sampling=sampling)
    out = np.asarray(out)
    for i, (h, w) in enumerate(geoms):
        _assert_equal_inside(out[i, :h, :w], _own_finish(bufs[i]), (h, w))
        assert not out[i, h:].any() and not out[i, :, w:].any(), (h, w)


def test_canvas_finish_writes_member_rows_whole_and_nothing_else():
    """Into a batch of a nonzero sentinel, two groups - one in a canvas
    smaller than the batch's rows, one in the delivered canvas - each with
    padding slots that hold a real image: every member row is its own
    finish inside its extent and 0 in the rest of its row, rows of no group
    keep the sentinel, and no padding slot writes anywhere, nor a group of
    padding alone."""
    from petastorm_tpu.jax.loader import _CanvasGroup

    geoms = [(37, 50), (48, 61), (21, 64), (60, 77), (53, 33)]
    bufs = [_encode(_textured(h, w, 30 + i), quality=90)
            for i, (h, w) in enumerate(geoms)]
    cells = []
    for buf in bufs:
        planes, qtab, _ = native_image.read_jpeg_coefficients(buf)
        cells.append((tuple(planes), qtab, None))
    sampling = tuple((h, v) for (h, v, _, _)
                     in native_image.jpeg_coef_layout(bufs[0]).components)
    extents = np.asarray(geoms, np.int32)
    n_out, rows_of = 8, {0: 5, 1: 0, 2: 3, 3: 1, 4: 4}
    out = jnp.full((n_out, 64, 80, 3), 77, jnp.uint8)
    for canvas, members, size in (((48, 64), [0, 1, 2], 4),
                                  ((64, 80), [3, 4], 4)):
        g = _CanvasGroup.pack(cells, members, extents, canvas, sampling, size)
        # padding slots that hold the last member's image, not the first's
        pad, last = g.rows < 0, len(members) - 1
        for p in g.planes:
            p[pad] = p[last]
        g.qtabs[pad], g.extents[pad] = g.qtabs[last], g.extents[last]
        rows = np.asarray([rows_of[int(r)] if r >= 0 else n_out
                           for r in g.rows], np.int32)
        out = finish_canvas(out, tuple(jnp.asarray(p) for p in g.planes),
                            jnp.asarray(g.qtabs), jnp.asarray(g.extents),
                            jnp.asarray(rows), canvas_hw=canvas,
                            sampling=sampling)
    # a group of padding alone writes nothing
    out = finish_canvas(out, tuple(jnp.asarray(p) for p in g.planes),
                        jnp.asarray(g.qtabs), jnp.asarray(g.extents),
                        jnp.full(len(g.rows), n_out, jnp.int32),
                        canvas_hw=canvas, sampling=sampling)
    out = np.asarray(out)
    for i, (h, w) in enumerate(geoms):
        row = out[rows_of[i]]
        _assert_equal_inside(row[:h, :w], _own_finish(bufs[i]), (h, w))
        assert not row[h:].any() and not row[:, w:].any(), (h, w)
    for r in sorted(set(range(n_out)) - set(rows_of.values())):
        assert (out[r] == 77).all(), r


@pytest.fixture(scope="module")
def canvas_ds(tmp_path_factory):
    """40 rows over 20 distinct geometries up to 64x64, 4:2:0."""
    geoms = _geometries(7, 20)
    schema = Schema("Canvas", [
        Field("idx", np.int64),
        Field("image", np.uint8, (None, None, 3),
              CompressedImageCodec("jpeg", quality=90))])
    rows = [{"idx": i, "image": _textured(*geoms[i % len(geoms)], seed=i)}
            for i in range(40)]
    url = str(tmp_path_factory.mktemp("canvas") / "ds")
    write_dataset(url, schema, rows, row_group_size_rows=8)
    return url, geoms


def _stored(url):
    """id -> stored jpeg bytes."""
    import glob
    import os

    import pyarrow.parquet as pq

    out = {}
    for f in sorted(glob.glob(os.path.join(url, "*.parquet"))):
        t = pq.read_table(f, columns=["idx", "image"])
        for i, b in zip(t.column("idx").to_pylist(),
                        t.column("image").to_pylist()):
            out[int(i)] = b
    return out


def _run(url, telemetry=None, batch_size=8, **kw):
    with make_batch_reader(url, shuffle_row_groups=False, num_epochs=1,
                           workers_count=1, telemetry=telemetry,
                           decode_placement={"image": "device-mixed"}) as r:
        with JaxDataLoader(r, batch_size=batch_size, fields=["idx", "image"],
                           pad_shapes={"image": CANVASES}, **kw) as ld:
            batches = [{k: np.asarray(v) for k, v in b.items()} for b in ld]
            return batches, ld.diagnostics


def test_loader_delivers_each_image_in_its_canvas(canvas_ds):
    """Through the loader: the delivered shape is the largest canvas, each
    image is its own finish inside its extent and 0 outside, and within the
    hybrid-decode contract of libjpeg's decode."""
    url, _ = canvas_ds
    batches, _ = _run(url)
    stored = _stored(url)
    seen = set()
    for b in batches:
        assert b["image"].shape == (8, 64, 64, 3)
        for k, i in enumerate(b["idx"]):
            h, w = b["image_extent"][k]
            img = b["image"][k]
            _assert_equal_inside(img[:h, :w], _own_finish(stored[int(i)]),
                                 int(i))
            ref = _cv2_decode(stored[int(i)])
            assert np.abs(ref.astype(int) - img[:h, :w].astype(int)).max() <= 6
            assert not img[h:].any() and not img[:, w:].any()
            seen.add(int(i))
    assert seen == set(range(40))


def test_delivered_extents_equal_stored_geometry(canvas_ds):
    url, geoms = canvas_ds
    batches, _ = _run(url)
    for b in batches:
        assert b["image_extent"].dtype == np.int32
        assert b["image_extent"].shape == (8, 2)
        for k, i in enumerate(b["idx"]):
            assert tuple(b["image_extent"][k]) == geoms[int(i) % len(geoms)]


def test_finish_programs_stay_fixed_as_geometries_stream(canvas_ds):
    """20 geometries stream through in 5 batches; the finish compiles once
    per canvas of the one chroma layout, all at the first batch."""
    url, geoms = canvas_ds
    tele = T.Telemetry()
    with make_batch_reader(url, shuffle_row_groups=False, num_epochs=2,
                           workers_count=1, telemetry=tele,
                           decode_placement={"image": "device-mixed"}) as r:
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"],
                           pad_shapes={"image": CANVASES}) as ld:
            programs = []
            for _ in ld:
                programs.append(
                    tele.snapshot()["counters"]["loader.mixed_finish_programs"])
            diag = ld.diagnostics
    assert len(programs) == 10
    assert set(programs) == {len(CANVASES)}
    assert diag["mixed_finish_programs"] == len(CANVASES)
    assert diag["mixed_decode_geometries"] == {"image": len(geoms)}


def test_mixed_pack_span_and_counters_in_telemetry(canvas_ds, tmp_path):
    """The host's padding is the 'mixed-pack' stage (on the profiler's
    clock as petastorm/mixed-pack); the pixel counters give the fill."""
    from tests.test_telemetry import _profiled_spans

    url, geoms = canvas_ds
    tele = T.Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run(url, telemetry=tele)
    finally:
        jax.profiler.stop_trace()
    c = tele.snapshot()["counters"]
    assert c["stage.mixed-pack.count"] == 5
    assert c["stage.mixed-pack.busy_s"] > 0
    valid = sum(h * w for h, w in (geoms[i % len(geoms)] for i in range(40)))
    assert c["loader.mixed_pixels_valid"] == valid
    assert valid < c["loader.mixed_pixels_canvas"] <= 40 * 64 * 64
    assert c["loader.mixed_finish_programs"] == len(CANVASES)
    names = {e["name"] for e in tele.trace.tail(2000)}
    assert "mixed-pack" in names
    assert "mixed-pack" in {name for _, name, *_ in _profiled_spans(tmp_path)}


def test_image_beyond_the_largest_canvas_is_refused(canvas_ds):
    url, _ = canvas_ds
    with make_batch_reader(url, num_epochs=1, workers_count=1,
                           decode_placement={"image": "device-mixed"}) as r:
        from petastorm_tpu.errors import PetastormTpuError

        with pytest.raises(PetastormTpuError, match="largest canvas"):
            JaxDataLoader(r, batch_size=8, fields=["idx", "image"],
                          pad_shapes={"image": [(32, 32, 3), (48, 48, 3)]})


# -- the crop inside each extent ---------------------------------------------


def _resample(img, box, out_hw):
    """float32 bilinear resample of ``img[top:top+h, left:left+w]`` to
    ``out_hw``, half-pixel centres, taps clamped to the box (PyTorch's
    ``interpolate`` over the cropped box), rows first."""
    top, left, bh, bw = (int(v) for v in box)
    crop = img[top:top + bh, left:left + bw].astype(np.float32)

    def taps(size, n):
        src = (np.float32(size) * np.float32(1.0 / n)
               * (np.arange(n, dtype=np.float32) + np.float32(0.5))
               - np.float32(0.5))
        src = np.maximum(src, np.float32(0.0))
        i0 = src.astype(np.int32)
        return i0, np.minimum(i0 + 1, size - 1), src - i0.astype(np.float32)

    y0, y1, ly = taps(bh, out_hw[0])
    x0, x1, lx = taps(bw, out_hw[1])
    ly, lx = ly[:, None, None], lx[None, :, None]
    r = crop[y0] * (np.float32(1.0) - ly) + crop[y1] * ly
    out = r[:, x0] * (np.float32(1.0) - lx) + r[:, x1] * lx
    return np.clip(np.round(out), 0, 255).astype(np.uint8)


def _box_ok(box, h, w, scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)):
    """Inside (h, w), and either a candidate of area in ``scale`` and aspect
    in ``ratio`` up to the rounding of its sides, or the centre fallback."""
    top, left, bh, bw = (int(v) for v in box)
    if not (bh >= 1 and bw >= 1 and top >= 0 and left >= 0
            and top + bh <= h and left + bw <= w):
        return False
    area_ok = ((bh - .5) * (bw - .5) <= scale[1] * h * w
               and (bh + .5) * (bw + .5) >= scale[0] * h * w)
    ratio_ok = ((bw - .5) / (bh + .5) <= ratio[1]
                and (bw + .5) / (bh - .5) >= ratio[0])
    in_ratio = w / h
    fw = round(h * ratio[1]) if in_ratio > ratio[1] else w
    fh = round(w / ratio[0]) if in_ratio < ratio[0] else h
    fallback = (bh, bw, top, left) == (fh, fw, (h - fh) // 2, (w - fw) // 2)
    return (area_ok and ratio_ok) or fallback


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_crop_boxes_lie_inside_their_extents(seed):
    """Every box inside its image, within scale and ratio (or torchvision's
    centre fallback, which the two extreme aspects force)."""
    rng = np.random.default_rng(seed)
    ext = np.stack([rng.integers(8, 200, 256), rng.integers(8, 200, 256)],
                   axis=1).astype(np.int32)
    ext[:2] = [[10, 190], [190, 10]]
    boxes = np.asarray(ops.crop_boxes(jax.random.PRNGKey(seed),
                                      jnp.asarray(ext)))
    assert boxes.dtype == np.int32 and boxes.shape == (256, 4)
    for box, (h, w) in zip(boxes, ext):
        assert _box_ok(box, h, w), (box, h, w)
    # the extreme aspects fall back to the centre crop, clamped in ratio
    assert tuple(boxes[0]) == (0, (190 - 13) // 2, 10, 13)
    assert tuple(boxes[1]) == ((190 - 13) // 2, 0, 13, 10)


@pytest.mark.parametrize("out_hw", [(32, 32), (24, 40)])
def test_crop_with_extents_resamples_the_unpadded_image(out_hw):
    """Each crop equals a float32 resample of its image without the
    canvas padding, over the same box; the padding (255 here) is never
    read.  Equal up to the last float rounding: XLA may fuse a tap's
    multiply and add where numpy rounds each, so a value within an ulp of
    .5 can round either way (under 1% of values, never by more than 1)."""
    rng = np.random.default_rng(5)
    n, H, W = 16, 72, 80
    ext = np.stack([rng.integers(12, H + 1, n), rng.integers(12, W + 1, n)],
                   axis=1).astype(np.int32)
    imgs = rng.integers(0, 200, (n, H, W, 3), dtype=np.uint8)
    for i, (h, w) in enumerate(ext):
        imgs[i, h:] = 255
        imgs[i, :, w:] = 255
    key = jax.random.PRNGKey(9)
    got = np.asarray(ops.random_resized_crop(jnp.asarray(imgs), key, out_hw,
                                             extents=jnp.asarray(ext)))
    boxes = np.asarray(ops.crop_boxes(key, jnp.asarray(ext)))
    assert got.shape == (n,) + out_hw + (3,)
    for i, (h, w) in enumerate(ext):
        want = _resample(imgs[i, :h, :w], boxes[i], out_hw)
        diff = np.abs(got[i].astype(int) - want.astype(int))
        assert diff.max() <= 1 and (diff > 0).mean() < 1e-2, i


def test_crop_with_extents_keeps_padding_rows_finite():
    """Rows of extent (0, 0), as a partial batch's padding delivers, crop
    to something finite instead of dividing by zero."""
    imgs = jnp.full((2, 16, 16, 3), 7, jnp.uint8)
    ext = jnp.asarray([[16, 16], [0, 0]], jnp.int32)
    out = np.asarray(ops.random_resized_crop(imgs, jax.random.PRNGKey(0),
                                             (8, 8), extents=ext))
    assert out.shape == (2, 8, 8, 3) and (out == 7).all()


def _previous_random_resized_crop(images, key, out_hw, scale=(0.08, 1.0),
                                  ratio=(3.0 / 4.0, 4.0 / 3.0),
                                  method="bilinear", antialias=False):
    """``ops.random_resized_crop`` as it was before ``extents``: the crop
    over the whole array."""
    from petastorm_tpu.ops.augment import _restore_dtype

    n, h, w, c = images.shape
    oh, ow = out_hw
    k_area, k_ratio, k_y, k_x = jax.random.split(key, 4)
    area_frac = jax.random.uniform(k_area, (n,), minval=scale[0],
                                   maxval=scale[1])
    log_r = jax.random.uniform(k_ratio, (n,), minval=jnp.log(ratio[0]),
                               maxval=jnp.log(ratio[1]))
    r = jnp.exp(log_r)
    area = area_frac * (h * w)
    crop_w = jnp.sqrt(area * r)
    crop_h = jnp.sqrt(area / r)
    crop_w = jnp.clip(crop_w, 1.0, float(w))
    crop_h = jnp.clip(crop_h, 1.0, float(h))
    y0 = jax.random.uniform(k_y, (n,)) * (h - crop_h)
    x0 = jax.random.uniform(k_x, (n,)) * (w - crop_w)
    x = images.astype(jnp.float32)

    def one(img, ch, cw, yy, xx):
        sy, sx = oh / ch, ow / cw
        return jax.image.scale_and_translate(
            img, (oh, ow, c), (0, 1), jnp.stack([sy, sx]),
            jnp.stack([-yy * sy, -xx * sx]), method=method,
            antialias=antialias)

    return _restore_dtype(jax.vmap(one)(x, crop_h, crop_w, y0, x0),
                          images.dtype)


def test_crop_without_extents_is_unchanged():
    """Without ``extents`` the op compiles the program it compiled before
    extents existed, and gives bit-identical crops: the image cells' step
    is untouched."""
    import functools

    images = jax.ShapeDtypeStruct((8, 48, 40, 3), jnp.uint8)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    old = jax.jit(functools.partial(_previous_random_resized_crop,
                                    out_hw=(24, 24)))

    def body(lowered):
        text = lowered.as_text()
        return text[text.index("func.func public @main"):]

    assert (body(ops.random_resized_crop.lower(images, key, (24, 24)))
            == body(old.lower(images, key)))
    x = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (8, 48, 40, 3), dtype=np.uint8))
    k = jax.random.PRNGKey(4)
    np.testing.assert_array_equal(
        np.asarray(ops.random_resized_crop(x, k, (24, 24))),
        np.asarray(old(x, k)))


def _previous_decode_coefficients(planes, qtabs, image_size, sampling):
    """``ops.jpeg.decode_coefficients`` as it was before the canvas finish
    (uint8 out, fancy upsampling): the 'device' placement's finish."""
    from petastorm_tpu.ops.jpeg import _YCC_TO_RGB, _idct_blocks, _upsample_to

    height, width = image_size
    max_h = max(s[0] for s in sampling)
    max_v = max(s[1] for s in sampling)
    comps = []
    for c, coefs in enumerate(planes):
        spatial = _idct_blocks(coefs, qtabs[..., c, :])
        h_samp, v_samp = sampling[c]
        ch = -(-height * v_samp // max_v)
        cw = -(-width * h_samp // max_h)
        spatial = spatial[..., :ch, :cw]
        comps.append(_upsample_to(spatial, (max_v // v_samp, max_h // h_samp),
                                  height, width, True))
    ycc = jnp.stack(comps, axis=-1)
    ycc = ycc - jnp.asarray([0.0, 128.0, 128.0], dtype=jnp.float32)
    out = jnp.matmul(ycc, jnp.asarray(_YCC_TO_RGB).T,
                     precision=jax.lax.Precision.HIGHEST)
    out = jnp.clip(jnp.round(out), 0, 255)
    return out.astype(jnp.uint8)


def test_device_finish_is_unchanged():
    """The 'device' placement's finish compiles the program it compiled
    before the canvas finish existed: the image cells' decode is
    untouched."""
    import functools

    sampling = ((2, 2), (1, 1), (1, 1))
    planes = (jax.ShapeDtypeStruct((8, 28, 28, 64), jnp.int16),
              jax.ShapeDtypeStruct((8, 14, 14, 64), jnp.int16),
              jax.ShapeDtypeStruct((8, 14, 14, 64), jnp.int16))
    qtabs = jax.ShapeDtypeStruct((8, 3, 64), jnp.uint16)
    old = jax.jit(functools.partial(_previous_decode_coefficients,
                                    image_size=(224, 224), sampling=sampling))

    def body(lowered):
        text = lowered.as_text()
        return text[text.index("func.func public @main"):]

    assert (body(decode_coefficients.lower(planes, qtabs,
                                           image_size=(224, 224),
                                           sampling=sampling))
            == body(old.lower(planes, qtabs)))
