"""decode_placement='device-mixed': on-chip decode of MIXED jpeg geometries.

Lifts the uniform-geometry restriction of the 'device' fast path: the host
pads each image's coefficient planes into the smallest of a fixed set of
canvases, and the chip finishes groups of images one canvas at a time - so
XLA compiles the finish once per (chroma layout, canvas), however many
geometries the data holds - into one static delivered shape.

Reference analog: the host decode handles any geometry per cell
(petastorm/codecs.py:92-118); this gets the same generality on the device
path.
"""

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from petastorm_tpu.errors import PetastormTpuError  # noqa: E402
from petastorm_tpu.native import image as native_image  # noqa: E402

if not native_image.available():
    pytest.skip("native image library unavailable", allow_module_level=True)

from petastorm_tpu.codecs import CompressedImageCodec  # noqa: E402
from petastorm_tpu.etl.writer import write_dataset  # noqa: E402
from petastorm_tpu.jax import JaxDataLoader  # noqa: E402
from petastorm_tpu.reader import make_batch_reader  # noqa: E402
from petastorm_tpu.schema import Field, Schema  # noqa: E402

from tests.test_jpeg_hybrid import _cv2_decode, _encode, _smooth_rgb  # noqa: E402

#: three geometries, interleaved so single rowgroups mix them
GEOMETRIES = [(64, 96), (48, 64), (32, 32)]
TARGET = (64, 96, 3)
N_ROWS = 24


@pytest.fixture(scope="module")
def mixed_ds(tmp_path_factory):
    schema = Schema("MixedGeo", [
        Field("idx", np.int64),
        Field("image", np.uint8, (None, None, 3),
              CompressedImageCodec("jpeg", quality=92)),
    ])
    rows = []
    for i in range(N_ROWS):
        h, w = GEOMETRIES[i % len(GEOMETRIES)]
        rows.append({"idx": i, "image": _smooth_rgb(h, w, seed=i)})
    url = str(tmp_path_factory.mktemp("mixed_geo") / "ds")
    write_dataset(url, schema, rows, row_group_size_rows=6)
    return url


def test_mixed_decode_matches_host_decode(mixed_ds):
    """Every geometry decodes on-device to within the hybrid-decode pixel
    contract of its host decode, padded to the static target - and the
    on-chip finish compiles a BOUNDED set of programs (one per canvas and
    chroma layout, not one per geometry)."""
    with make_batch_reader(mixed_ds, shuffle_row_groups=False, num_epochs=2,
                           decode_placement={"image": "device-mixed"}) as r:
        assert r.device_decode_mixed == frozenset({"image"})
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"],
                           pad_shapes={"image": TARGET}) as loader:
            got = {}
            for b in loader:
                imgs = np.asarray(b["image"])
                assert imgs.shape == (8,) + TARGET and imgs.dtype == np.uint8
                for k, i in enumerate(np.asarray(b["idx"])):
                    got.setdefault(int(i), []).append(imgs[k])
            diag = loader.diagnostics
    assert sorted(got) == list(range(N_ROWS))
    assert all(len(v) == 2 for v in got.values())  # both epochs delivered

    # bounded compiles: one finish program for the one canvas and 4:2:0
    # layout, across 3 geometries, 2 epochs and 6 batches
    assert diag["mixed_finish_programs"] == 1
    assert diag["mixed_decode_geometries"] == {"image": len(GEOMETRIES)}

    for i in range(N_ROWS):
        h, w = GEOMETRIES[i % len(GEOMETRIES)]
        ref = _cv2_decode(_encode(_smooth_rgb(h, w, seed=i), quality=92))
        dev = got[i][0]
        diff = np.abs(ref.astype(int) - dev[:h, :w].astype(int))
        assert diff.max() <= 6 and diff.mean() < 1.0, f"idx {i} ({h}x{w})"
        # the pad region is exactly zero
        assert dev[h:].sum() == 0 and dev[:, w:].sum() == 0


def test_mixed_subsampling_within_one_size(tmp_path):
    """Same pixel size but different chroma subsampling = different
    coefficient geometry; both must decode in one dataset."""
    s444 = getattr(cv2, "IMWRITE_JPEG_SAMPLING_FACTOR_444", None)
    if s444 is None:
        pytest.skip("cv2 build lacks sampling-factor control")
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.etl.writer import stamp_dataset_metadata

    schema = Schema("MixedSamp", [
        Field("idx", np.int64),
        Field("image", np.uint8, (32, 32, 3), CompressedImageCodec("jpeg"))])
    bufs = [_encode(_smooth_rgb(32, 32, seed=i),
                    sampling=(s444 if i % 2 else None)) for i in range(8)]
    url = str(tmp_path / "ds")
    os.makedirs(url)
    table = pa.Table.from_pylist(
        [{"idx": i, "image": b} for i, b in enumerate(bufs)],
        schema=schema.as_arrow_schema())
    pq.write_table(table, os.path.join(url, "part-00000.parquet"),
                   row_group_size=4)
    stamp_dataset_metadata(url, schema)

    with make_batch_reader(url, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        # fixed schema shape: the target comes from the schema, no pad_shapes
        with JaxDataLoader(r, batch_size=4, fields=["idx", "image"]) as loader:
            batches = list(loader)
            diag = loader.diagnostics
    assert diag["mixed_decode_geometries"] == {"image": 2}
    by_idx = {int(i): np.asarray(b["image"])[k]
              for b in batches for k, i in enumerate(np.asarray(b["idx"]))}
    for i in range(8):
        ref = _cv2_decode(bufs[i])
        diff = np.abs(ref.astype(int) - by_idx[i].astype(int))
        assert diff.max() <= 6 and diff.mean() < 1.0


def test_mixed_requires_static_target(mixed_ds):
    """A variable-shape mixed field needs canvases; the largest is the
    static delivered shape, so it must hold every other canvas and every
    geometry the dataset declares."""
    with make_batch_reader(mixed_ds, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        with pytest.raises(PetastormTpuError, match="pad_shapes canvases"):
            JaxDataLoader(r, batch_size=8, fields=["idx", "image"])
        with pytest.raises(PetastormTpuError, match="hold every other"):
            JaxDataLoader(r, batch_size=8, fields=["idx", "image"],
                          pad_shapes={"image": [(32, 96, 3), (64, 64, 3)]})
        with pytest.raises(PetastormTpuError,
                           match="larger than the largest canvas"):
            JaxDataLoader(r, batch_size=8, fields=["idx", "image"],
                          pad_shapes={"image": [(32, 32, 3), (48, 64, 3)]})


def test_declared_geometries_stamped_at_write(mixed_ds):
    """write_dataset stamps the dataset-level geometry contract for
    variable-shape image fields; the reader exposes it."""
    with make_batch_reader(mixed_ds, num_epochs=1) as r:
        declared = r.declared_geometries
    assert set(declared) == {"image"}
    assert sorted(declared["image"]) == sorted(
        (h, w, 3) for h, w in GEOMETRIES)


def test_mixed_on_mesh_decodes_and_bounds_compiles(mixed_ds):
    """'device-mixed' works across a mesh.  The decode is
    host-local (canvas groups may differ per host), delivery scatters the
    decoded rows and their extents over the mesh as global arrays, and the
    compile count stays at one program per canvas and chroma layout."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(mixed_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings={"idx": P("data"), "image": P("data")},
                           fields=["idx", "image"],
                           pad_shapes={"image": TARGET}) as loader:
            got = {}
            for b in loader:
                assert b["image"].shape == (8,) + TARGET
                assert b["image"].sharding.spec == P("data")
                assert len(b["image"].sharding.device_set) == 8
                assert b["image_extent"].shape == (8, 2)
                assert b["image_extent"].sharding.spec == P("data")
                imgs = np.asarray(b["image"])
                for k, i in enumerate(np.asarray(b["idx"])):
                    got[int(i)] = imgs[k]
            diag = loader.diagnostics
    assert sorted(got) == list(range(N_ROWS))
    assert diag["mixed_finish_programs"] == 1  # bounded compiles on the mesh
    assert diag["mixed_decode_geometries"] == {"image": len(GEOMETRIES)}
    assert diag["declared_geometries"] == {"image": len(GEOMETRIES)}
    for i in range(N_ROWS):
        h, w = GEOMETRIES[i % len(GEOMETRIES)]
        ref = _cv2_decode(_encode(_smooth_rgb(h, w, seed=i), quality=92))
        diff = np.abs(ref.astype(int) - got[i][:h, :w].astype(int))
        assert diff.max() <= 6 and diff.mean() < 1.0, f"idx {i} ({h}x{w})"


def test_mixed_on_mesh_partial_tail_padded(mixed_ds):
    """drop_last=False on a mesh: the partial final mixed batch zero-pads to
    the static shape and carries '_valid_rows' + a zero valid mask tail."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(mixed_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        with JaxDataLoader(r, batch_size=16, mesh=mesh, drop_last=False,
                           shardings={"idx": P("data"), "image": P("data")},
                           fields=["idx", "image"],
                           pad_shapes={"image": TARGET},
                           valid_mask_field="mask") as loader:
            batches = list(loader)
    assert len(batches) == 2  # 24 rows = 16 + 8(+8 pad)
    tail = batches[-1]
    assert tail["_valid_rows"] == 8
    assert np.asarray(tail["mask"]).tolist() == [1.0] * 8 + [0.0] * 8
    assert np.asarray(tail["image"])[8:].sum() == 0  # pad rows all zero


def test_mixed_on_mesh_trailing_axes_rejected(mixed_ds):
    """Only the batch axis may shard a mixed field (the decode is host-local;
    image axes cannot span hosts)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    with make_batch_reader(mixed_ds, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        with pytest.raises(PetastormTpuError, match="only the batch axis"):
            JaxDataLoader(r, batch_size=8, mesh=mesh,
                          shardings={"idx": P("data"),
                                     "image": P("data", "model")},
                          fields=["idx", "image"],
                          pad_shapes={"image": TARGET})


def test_mixed_on_mesh_replicated_single_host_works(mixed_ds):
    """A batch-replicated spec is feasible on a single host (the host holds
    the whole batch); delivery replicates the decoded rows to every device."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(mixed_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings={"idx": P("data"), "image": P()},
                           fields=["idx", "image"],
                           pad_shapes={"image": TARGET}) as loader:
            b = next(iter(loader))
    assert b["image"].shape == (8,) + TARGET
    assert b["image"].sharding.is_fully_replicated
    # replicated delivery carries the same pixels as the sharded path
    i0 = int(np.asarray(b["idx"])[0])
    h, w = GEOMETRIES[i0 % len(GEOMETRIES)]
    ref = _cv2_decode(_encode(_smooth_rgb(h, w, seed=i0), quality=92))
    assert np.abs(ref.astype(int)
                  - np.asarray(b["image"])[0, :h, :w].astype(int)).max() <= 6


def test_mixed_scatter_layout_rejected_across_processes(mixed_ds):
    """When the batch spans processes (local rows < global batch), a
    batch-replicated spec must fail AT CONSTRUCTION with the contract error,
    not an opaque shape error from make_array_from_single_device_arrays.
    Single-process tests cannot make jax report multiple processes, so the
    multi-host geometry is modelled by the one quantity the check consumes:
    ``_local_rows`` < ``_global_batch``."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(mixed_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings={"idx": P("data"), "image": P()},
                           fields=["idx", "image"],
                           pad_shapes={"image": TARGET}) as loader:
            loader._local_rows = 4  # this host owns half the global batch
            with pytest.raises(PetastormTpuError,
                               match="batch axis to be sharded"):
                loader._validate_mixed_scatter_layout("image")
            # a sharded spec whose shards cover more rows than the host owns
            # trips the coverage check with the mesh/spec in the message
            loader._specs = {"idx": P("data"), "image": P("data")}
            with pytest.raises(PetastormTpuError, match="host owns 4"):
                loader._validate_mixed_scatter_layout("image")


def test_uniform_device_path_still_guides_to_mixed(mixed_ds):
    """The uniform 'device' path on a mixed dataset keeps failing loudly,
    now pointing at 'device-mixed'."""
    with pytest.raises(PetastormTpuError, match="device-mixed"):
        make_batch_reader(mixed_ds, num_epochs=1,
                          decode_placement={"image": "device"})
