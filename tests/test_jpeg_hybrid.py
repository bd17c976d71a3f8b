"""Hybrid JPEG decode: host entropy half + device IDCT/color half.

Reference analog: the all-host CompressedImageCodec decode
(petastorm/codecs.py:92-118, tests/test_codec_compressed_image.py); the hybrid
split is this framework's on-device-decode design (SURVEY.md section 7 step 8).
"""

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from petastorm_tpu.errors import CodecError  # noqa: E402
from petastorm_tpu.native import image as native_image  # noqa: E402

if not native_image.available():
    pytest.skip("native image library unavailable", allow_module_level=True)


def _smooth_rgb(h, w, seed=0):
    x, y = np.meshgrid(np.arange(w), np.arange(h))
    img = np.stack([
        (np.sin(x / (9.0 + seed)) + np.cos(y / 7.0)) * 60 + 120,
        (np.sin(x / 5.0) + seed * 0.1) * 50 + 128,
        np.cos(x / 11.0) * np.sin(y / 13.0) * 55 + 120,
    ], -1)
    return img.clip(0, 255).astype(np.uint8)


def _encode(img, quality=90, sampling=None):
    params = [int(cv2.IMWRITE_JPEG_QUALITY), quality]
    if sampling is not None:
        params += [int(cv2.IMWRITE_JPEG_SAMPLING_FACTOR), sampling]
    src = img if img.ndim == 2 else cv2.cvtColor(img, cv2.COLOR_RGB2BGR)
    ok, enc = cv2.imencode(".jpeg", src, params)
    assert ok
    return enc.tobytes()


def _cv2_decode(buf, gray=False):
    flag = cv2.IMREAD_GRAYSCALE if gray else cv2.IMREAD_COLOR
    out = cv2.imdecode(np.frombuffer(buf, np.uint8), flag)
    return out if gray else cv2.cvtColor(out, cv2.COLOR_BGR2RGB)


def test_coef_layout_and_read():
    buf = _encode(_smooth_rgb(64, 96))
    layout = native_image.jpeg_coef_layout(buf)
    assert (layout.width, layout.height) == (96, 64)
    assert len(layout.components) == 3
    h0, v0, bw0, bh0 = layout.components[0]  # luma, 4:2:0 by default
    assert (bw0, bh0) == (96 // 8, 64 // 8)
    planes, qtabs, _ = native_image.read_jpeg_coefficients(buf)
    assert planes[0].shape == (bh0, bw0, 64) and planes[0].dtype == np.int16
    assert qtabs.shape == (3, 64) and qtabs.min() >= 1
    # DC of the first luma block, dequantized, reconstructs the block mean
    dc = float(planes[0][0, 0, 0]) * float(qtabs[0, 0]) / 8.0 + 128.0
    ref_mean = _cv2_decode(buf)[..., :].astype(float)
    y = (0.299 * ref_mean[..., 0] + 0.587 * ref_mean[..., 1]
         + 0.114 * ref_mean[..., 2])
    assert abs(dc - y[:8, :8].mean()) < 3.0


@pytest.mark.parametrize("sampling,name", [
    (None, "420-default"),
    (getattr(cv2, "IMWRITE_JPEG_SAMPLING_FACTOR_444", None), "444"),
    (getattr(cv2, "IMWRITE_JPEG_SAMPLING_FACTOR_422", None), "422"),
])
def test_hybrid_matches_cv2_color(sampling, name):
    if name != "420-default" and sampling is None:
        pytest.skip("cv2 build lacks sampling-factor control")
    from petastorm_tpu.ops.jpeg import decode_jpeg_column

    bufs = [_encode(_smooth_rgb(64, 96, seed=i), sampling=sampling)
            for i in range(3)]
    ours = np.asarray(decode_jpeg_column(bufs))
    refs = np.stack([_cv2_decode(b) for b in bufs])
    assert ours.shape == refs.shape == (3, 64, 96, 3)
    diff = np.abs(ours.astype(int) - refs.astype(int))
    assert diff.max() <= 6, (name, diff.max())
    assert diff.mean() < 1.0, (name, diff.mean())


def test_hybrid_matches_cv2_on_busy_images():
    """Noise images push IDCT samples far outside [0, 255]: the device half
    must clamp each component sample as libjpeg does before upsampling and
    colour conversion, or busy pixels drift far from the host decode."""
    from petastorm_tpu.ops.jpeg import decode_jpeg_column

    rng = np.random.default_rng(0)
    bufs = [_encode(rng.integers(0, 255, (224, 224, 3), dtype=np.uint8))
            for _ in range(8)]
    ours = np.asarray(decode_jpeg_column(bufs))
    refs = np.stack([_cv2_decode(b) for b in bufs])
    diff = np.abs(ours.astype(int) - refs.astype(int))
    assert diff.max() <= 4, diff.max()
    assert diff.mean() < 1.0, diff.mean()


def test_hybrid_grayscale():
    from petastorm_tpu.ops.jpeg import decode_jpeg_column

    imgs = [_smooth_rgb(40, 56, seed=i)[..., 0] for i in range(2)]
    bufs = [_encode(im) for im in imgs]
    ours = np.asarray(decode_jpeg_column(bufs))
    refs = np.stack([_cv2_decode(b, gray=True) for b in bufs])
    assert ours.shape == refs.shape == (2, 40, 56)
    assert np.abs(ours.astype(int) - refs.astype(int)).max() <= 4


def test_hybrid_non_multiple_of_8_and_float_output():
    import jax.numpy as jnp

    from petastorm_tpu.ops.jpeg import decode_jpeg_column

    img = _smooth_rgb(37, 53)  # forces block padding + crop
    buf = _encode(img)
    ours = np.asarray(decode_jpeg_column([buf]))
    ref = _cv2_decode(buf)
    assert ours.shape == (1, 37, 53, 3)
    assert np.abs(ours[0].astype(int) - ref.astype(int)).max() <= 6
    f = np.asarray(decode_jpeg_column([buf], out_dtype=jnp.float32))
    assert f.dtype == np.float32
    # float path skips the round/clip: same values within rounding
    assert np.abs(f[0] - ref.astype(np.float32)).max() <= 6.5


def test_column_geometry_mismatch_raises():
    bufs = [_encode(_smooth_rgb(64, 96)), _encode(_smooth_rgb(32, 96))]
    with pytest.raises(CodecError, match="geometry"):
        native_image.read_jpeg_coefficients_column(bufs)


def test_non_jpeg_raises():
    with pytest.raises(CodecError):
        native_image.jpeg_coef_layout(b"\x89PNG\r\n\x1a\nnot a jpeg")


def test_decode_coefficients_is_jittable_batch():
    """The device half traces once per geometry (static shapes) - the property
    the JAX ingest loop needs."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops.jpeg import decode_coefficients

    bufs = [_encode(_smooth_rgb(64, 96, seed=i)) for i in range(2)]
    planes, qtabs, layout = native_image.read_jpeg_coefficients_column(bufs)
    sampling = tuple((h, v) for (h, v, _, _) in layout.components)
    args = (tuple(jnp.asarray(p) for p in planes), jnp.asarray(qtabs))
    kw = dict(image_size=(layout.height, layout.width), sampling=sampling)
    out1 = decode_coefficients(*args, **kw)
    n_before = decode_coefficients._cache_size()
    out2 = decode_coefficients(*args, **kw)
    assert decode_coefficients._cache_size() == n_before  # no retrace
    assert isinstance(out1, jax.Array)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))


# -- end-to-end: decode_placement='device' through reader + jax loader --------


@pytest.fixture(scope="module")
def jpeg_ds(tmp_path_factory):
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.schema import Field, Schema

    schema = Schema("JpegDs", [
        Field("idx", np.int64),
        Field("image", np.uint8, (64, 96, 3), CompressedImageCodec("jpeg", quality=90)),
    ])
    rows = [{"idx": i, "image": _smooth_rgb(64, 96, seed=i)} for i in range(32)]
    url = str(tmp_path_factory.mktemp("jpeg_ds") / "ds")
    write_dataset(url, schema, rows, row_group_size_rows=8)
    return url


def test_device_decode_end_to_end(jpeg_ds):
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    with make_batch_reader(jpeg_ds, shuffle_row_groups=False, num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"]) as loader:
            host_batches = [{k: np.asarray(v) for k, v in b.items()}
                            for b in loader]
    with make_batch_reader(jpeg_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        assert r.device_decode_fields == ["image"]
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"]) as loader:
            dev_batches = [{k: np.asarray(v) for k, v in b.items()}
                           for b in loader]
    assert len(host_batches) == len(dev_batches) == 4
    # thread-pool results arrive in completion order: compare by idx
    host_by_idx = {int(i): hb["image"][k]
                   for hb in host_batches for k, i in enumerate(hb["idx"])}
    dev_by_idx = {int(i): db["image"][k]
                  for db in dev_batches for k, i in enumerate(db["idx"])}
    assert sorted(host_by_idx) == sorted(dev_by_idx) == list(range(32))
    for db in dev_batches:
        assert db["image"].shape == (8, 64, 96, 3) and db["image"].dtype == np.uint8
    for i in range(32):
        diff = np.abs(host_by_idx[i].astype(int) - dev_by_idx[i].astype(int))
        assert diff.max() <= 6 and diff.mean() < 1.0


def test_device_decode_on_mesh(jpeg_ds):
    import jax
    from jax.sharding import Mesh, PartitionSpec

    assert len(jax.devices()) == 8, "conftest forces the 8-device CPU platform"

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
    with make_batch_reader(jpeg_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=16, mesh=mesh,
                           shardings={"idx": PartitionSpec("data"),
                                      "image": PartitionSpec("data")},
                           fields=["idx", "image"]) as loader:
            batches = list(loader)
    assert len(batches) == 2
    img = batches[0]["image"]
    assert img.shape == (16, 64, 96, 3)
    assert img.sharding.spec == PartitionSpec("data")
    # values survive the sharded decode
    host = np.asarray(img)
    assert host.std() > 10  # real image content, not zeros


def test_device_decode_rejects_png(tmp_path):
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.errors import PetastormTpuError
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema

    schema = Schema("P", [Field("image", np.uint8, (16, 16, 3),
                                CompressedImageCodec("png"))])
    url = str(tmp_path / "ds")
    write_dataset(url, schema, [{"image": _smooth_rgb(16, 16)}])
    with pytest.raises(PetastormTpuError, match="jpeg"):
        make_batch_reader(url, decode_placement={"image": "device"})
    with pytest.raises(PetastormTpuError,
                       match="'host', 'device', 'device-mixed' or 'auto'"):
        make_batch_reader(url, decode_placement={"image": "chip"})


def test_device_decode_rejects_non_jax_consumption(jpeg_ds):
    """Row iteration and the torch loaders would yield object-dtype jpeg bytes
    where the schema promises pixels; both must refuse loudly."""
    from petastorm_tpu.errors import PetastormTpuError
    from petastorm_tpu.pytorch import DataLoader
    from petastorm_tpu.reader import make_batch_reader, make_reader

    with make_reader(jpeg_ds, num_epochs=1,
                     decode_placement={"image": "device"}) as r:
        with pytest.raises(PetastormTpuError, match="JaxDataLoader"):
            next(r)
    with make_batch_reader(jpeg_ds, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with pytest.raises(PetastormTpuError, match="decode_placement='host'"):
            DataLoader(r, batch_size=4)


def test_grayscale_hw1_field_keeps_rank(tmp_path):
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema

    schema = Schema("G", [Field("image", np.uint8, (32, 48, 1),
                                CompressedImageCodec("jpeg"))])
    rows = [{"image": _smooth_rgb(32, 48, seed=i)[..., :1]} for i in range(8)]
    url = str(tmp_path / "ds")
    write_dataset(url, schema, rows)
    with make_batch_reader(url, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=8, fields=["image"]) as loader:
            b = next(iter(loader))
    assert b["image"].shape == (8, 32, 48, 1)  # schema rank honored


def test_wrong_size_jpeg_raises_clear_error(jpeg_ds, tmp_path):
    """Stored jpegs that contradict the schema shape fail loudly in the
    worker's entropy half, not with a silent wrong-shape batch."""
    import shutil

    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.errors import CodecError
    from petastorm_tpu.etl.writer import stamp_dataset_metadata
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema

    url = str(tmp_path / "ds")
    shutil.copytree(jpeg_ds, url)
    lying = Schema("JpegDs", [
        Field("idx", np.int64),
        Field("image", np.uint8, (32, 96, 3), CompressedImageCodec("jpeg"))])
    stamp_dataset_metadata(url, lying)  # stored jpegs are really 64x96
    from petastorm_tpu.errors import PetastormTpuError

    with make_batch_reader(url, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=4, fields=["image"]) as loader:
            # worker failures surface as WorkerError(PetastormTpuError)
            # carrying the remote CodecError traceback in the message
            with pytest.raises(PetastormTpuError, match="schema says"):
                list(loader)


def test_mixed_geometry_rejected_with_guidance(jpeg_ds, monkeypatch):
    """Non-uniform jpeg geometry cannot take the device path (the on-chip
    decode compiles per geometry); the worker refuses with migration
    guidance instead of silently degrading."""
    from petastorm_tpu.errors import CodecError
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    def boom(cells, **kw):
        raise CodecError("geometry differs (simulated)")

    monkeypatch.setattr("petastorm_tpu.native.image.read_jpeg_coefficients_column",
                        boom)
    from petastorm_tpu.errors import PetastormTpuError

    with make_batch_reader(jpeg_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"]) as loader:
            with pytest.raises(PetastormTpuError, match="decode_placement='host'"):
                list(loader)


def _write_raw_jpeg_ds(tmp_path, bufs, rows_per_group):
    """Dataset with hand-encoded jpeg bytes (writer would re-encode), so
    tests can control per-cell subsampling."""
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.writer import stamp_dataset_metadata
    from petastorm_tpu.schema import Field, Schema

    schema = Schema("Mixed", [
        Field("idx", np.int64),
        Field("image", np.uint8, (64, 96, 3), CompressedImageCodec("jpeg"))])
    url = str(tmp_path / "mixed_ds")
    os.makedirs(url)
    table = pa.Table.from_pylist(
        [{"idx": i, "image": b} for i, b in enumerate(bufs)],
        schema=schema.as_arrow_schema())
    pq.write_table(table, os.path.join(url, "part-00000.parquet"),
                   row_group_size=rows_per_group)
    stamp_dataset_metadata(url, schema)
    return url


def test_mixed_geometry_within_rowgroup_diagnosed(tmp_path):
    """A rowgroup mixing 4:2:0 and 4:4:4 jpegs fails in the worker with the
    offending cell named and host-decode guidance - not an opaque rc."""
    s444 = getattr(cv2, "IMWRITE_JPEG_SAMPLING_FACTOR_444", None)
    if s444 is None:
        pytest.skip("cv2 build lacks sampling-factor control")
    from petastorm_tpu.errors import PetastormTpuError
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    bufs = [_encode(_smooth_rgb(64, 96, seed=i)) for i in range(6)]
    bufs[3] = _encode(_smooth_rgb(64, 96, seed=3), sampling=s444)
    url = _write_raw_jpeg_ds(tmp_path, bufs, rows_per_group=6)
    with make_batch_reader(url, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=6, fields=["image"]) as loader:
            with pytest.raises(PetastormTpuError,
                               match=r"cell 3 has geometry.*"
                                     r"decode_placement='host'"):
                list(loader)


def test_mixed_geometry_across_rowgroups_guided(tmp_path):
    """Uniform rowgroups with different subsampling: batch assembly spanning
    the boundary must raise the guided error, not a numpy shape mismatch."""
    s444 = getattr(cv2, "IMWRITE_JPEG_SAMPLING_FACTOR_444", None)
    if s444 is None:
        pytest.skip("cv2 build lacks sampling-factor control")
    from petastorm_tpu.errors import CodecError
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    bufs = ([_encode(_smooth_rgb(64, 96, seed=i)) for i in range(4)]
            + [_encode(_smooth_rgb(64, 96, seed=i), sampling=s444)
               for i in range(4, 8)])
    url = _write_raw_jpeg_ds(tmp_path, bufs, rows_per_group=4)
    with make_batch_reader(url, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=8, fields=["image"]) as loader:
            with pytest.raises(CodecError, match="decode_placement='host'"):
                list(loader)


def test_corrupt_jpeg_cell_diagnosed(tmp_path):
    """A truncated jpeg cell is reported as corruption (host decode would
    fail too), NOT as a geometry-uniformity problem."""
    from petastorm_tpu.errors import PetastormTpuError
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    bufs = [_encode(_smooth_rgb(64, 96, seed=i)) for i in range(4)]
    bufs[2] = bufs[2][:40]  # truncate mid-header
    url = _write_raw_jpeg_ds(tmp_path, bufs, rows_per_group=4)
    with make_batch_reader(url, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=4, fields=["image"]) as loader:
            with pytest.raises(PetastormTpuError,
                               match="corrupt or truncated"):
                list(loader)


def test_decode_placement_validation_errors(jpeg_ds):
    from petastorm_tpu.errors import PetastormTpuError
    from petastorm_tpu.reader import make_batch_reader

    with pytest.raises(PetastormTpuError, match="not in"):
        make_batch_reader(jpeg_ds, decode_placement={"imge": "host"})  # typo
    with pytest.raises(PetastormTpuError, match="not being read"):
        make_batch_reader(jpeg_ds, schema_fields=["idx"],
                          decode_placement={"image": "device"})
    from petastorm_tpu.predicates import in_lambda
    with pytest.raises(PetastormTpuError, match="coefficient planes"):
        make_batch_reader(jpeg_ds, decode_placement={"image": "device"},
                          predicate=in_lambda(["image"], lambda image: True))


def test_progressive_jpeg_hybrid_decode():
    """jpeg_read_coefficients runs the full entropy decode, so progressive
    streams (multi-scan) work identically to baseline."""
    from petastorm_tpu.ops.jpeg import decode_jpeg_column

    img = _smooth_rgb(64, 96)
    prog = int(getattr(cv2, "IMWRITE_JPEG_PROGRESSIVE", -1))
    if prog < 0:
        pytest.skip("cv2 build lacks progressive encoding control")
    ok, enc = cv2.imencode(".jpeg", cv2.cvtColor(img, cv2.COLOR_RGB2BGR),
                           [int(cv2.IMWRITE_JPEG_QUALITY), 90, prog, 1])
    assert ok
    buf = enc.tobytes()
    ours = np.asarray(decode_jpeg_column([buf]))[0]
    ref = _cv2_decode(buf)
    assert np.abs(ours.astype(int) - ref.astype(int)).max() <= 6


def test_device_decode_with_process_pool(jpeg_ds):
    """Coefficient-plane columns ride the process pool's shm transport
    zero-copy (fixed-shape int16/uint16/int32 arrays)."""
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    with make_batch_reader(jpeg_ds, shuffle_row_groups=False, num_epochs=1,
                           reader_pool_type="process", workers_count=2,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"]) as loader:
            batches = list(loader)
    assert len(batches) == 4
    assert all(b["image"].shape == (8, 64, 96, 3) for b in batches)
    seen = sorted(int(i) for b in batches for i in np.asarray(b["idx"]))
    assert seen == list(range(32))


def test_weighted_sampling_propagates_device_decode(jpeg_ds):
    """A weighted mix of device-decode readers feeds the jax loader (the
    coefficient-plane columns need the loader's on-chip finish), and the
    row path refuses, like a plain Reader."""
    from petastorm_tpu.errors import PetastormTpuError
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.weighted_sampling import WeightedSamplingReader

    r1 = make_batch_reader(jpeg_ds, num_epochs=1, shuffle_row_groups=False,
                           decode_placement={"image": "device"})
    r2 = make_batch_reader(jpeg_ds, num_epochs=1, shuffle_row_groups=False,
                           decode_placement={"image": "device"})
    mixed = WeightedSamplingReader([r1, r2], [0.5, 0.5], seed=0)
    assert mixed.device_decode_fields == ["image"]
    with pytest.raises(PetastormTpuError, match="JaxDataLoader"):
        next(mixed)
    with mixed:
        with JaxDataLoader(mixed, batch_size=8,
                           fields=["idx", "image"]) as loader:
            b = next(iter(loader))
    assert np.asarray(b["image"]).shape == (8, 64, 96, 3)

    # mismatched placement across sub-readers is refused up front
    r3 = make_batch_reader(jpeg_ds, num_epochs=1,
                           decode_placement={"image": "device"})
    r4 = make_batch_reader(jpeg_ds, num_epochs=1)
    try:
        with pytest.raises(PetastormTpuError, match="decode_placement"):
            WeightedSamplingReader([r3, r4], [0.5, 0.5])
    finally:
        for r in (r3, r4):
            r.stop(); r.join()


def test_producer_error_winds_down_pipeline(jpeg_ds):
    """A terminal producer error must stop the reader/executor/assembly
    threads even WITHOUT the context manager - no spinning threads left."""
    import threading
    import time

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    before = threading.active_count()
    r = make_batch_reader(jpeg_ds, num_epochs=None, shuffle_row_groups=False)
    loader = JaxDataLoader(r, batch_size=4, fields=["idx"],
                           transform_fn=lambda cols: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        next(iter(loader))
    deadline = time.monotonic() + 20
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.1)
    assert threading.active_count() <= before, "producer threads kept running"


def test_copy_dataset_migrates_mixed_geometry_for_device_decode(tmp_path):
    """The guided migration actually works: a mixed-subsampling dataset that
    the device path refuses reads fine after petastorm-tpu-copy-dataset
    re-encodes it (uniform geometry), matching the original pixels."""
    s444 = getattr(cv2, "IMWRITE_JPEG_SAMPLING_FACTOR_444", None)
    if s444 is None:
        pytest.skip("cv2 build lacks sampling-factor control")
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.tools.copy_dataset import copy_dataset

    bufs = ([_encode(_smooth_rgb(64, 96, seed=i)) for i in range(4)]
            + [_encode(_smooth_rgb(64, 96, seed=i), sampling=s444)
               for i in range(4, 8)])
    src = _write_raw_jpeg_ds(tmp_path, bufs, rows_per_group=4)
    dst = str(tmp_path / "uniform_ds")
    assert copy_dataset(src, dst, jpeg_quality=95) == 8

    with make_batch_reader(dst, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"]) as loader:
            b = next(iter(loader))
    imgs, idxs = np.asarray(b["image"]), np.asarray(b["idx"])
    assert imgs.shape == (8, 64, 96, 3)
    by_idx = {int(i): imgs[k] for k, i in enumerate(idxs)}
    for i in range(8):
        want = _smooth_rgb(64, 96, seed=i)
        # two lossy hops (original jpeg + re-encode at q95): still close
        assert np.abs(by_idx[i].astype(int) - want.astype(int)).mean() < 3.0
