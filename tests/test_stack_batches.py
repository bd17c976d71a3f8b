"""stack_batches=K: scan-feed delivery as a first-class loader capability.

The lax.scan dispatch-amortization win moves from
example code into ``JaxDataLoader`` - ONE ``(K, batch, ...)`` transfer per K
steps, with drain/resume and the valid-mask contract defined at stack
granularity.  Reference analog: none (the reference feeds BatchedDataLoader
one batch per step, petastorm/pytorch.py:257-367).
"""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.jax import JaxDataLoader
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.schema import Field, Schema

SCHEMA = Schema("Stack", [
    Field("idx", np.int64),
    Field("vec", np.float32, (6,)),
    Field("tag", np.dtype("object")),
])
N_ROWS = 64


@pytest.fixture(scope="module")
def stack_ds(tmp_path_factory):
    url = str(tmp_path_factory.mktemp("stack") / "ds")
    rng = np.random.default_rng(0)
    write_dataset(url, SCHEMA,
                  [{"idx": i, "vec": rng.standard_normal(6).astype(np.float32),
                    "tag": f"t{i}"} for i in range(N_ROWS)],
                  row_group_size_rows=8)
    return url


def test_stack_single_device_shapes_and_order(stack_ds):
    # serial pool: rowgroups arrive in ventilation order, so the delivered
    # order is the exact feed order the stack must preserve
    reader = make_reader(stack_ds, shuffle_row_groups=False,
                         reader_pool_type="serial",
                         schema_fields=["idx", "vec"])
    with JaxDataLoader(reader, batch_size=8, fields=["idx", "vec"],
                       stack_batches=4) as loader:
        units = list(loader)
        diag = loader.diagnostics
    assert len(units) == 2  # 8 batches of 8 rows -> 2 stacks of 4
    u = units[0]
    assert isinstance(u["idx"], jax.Array) and u["idx"].shape == (4, 8)
    assert u["idx"].dtype == np.int32  # promotion happens once, on the stack
    assert u["vec"].shape == (4, 8, 6)
    assert "_valid_rows" not in u  # all steps full
    flat = np.concatenate([np.asarray(u["idx"]).reshape(-1) for u in units])
    assert flat.tolist() == list(range(N_ROWS))  # stack preserves feed order
    assert diag["stack_batches"] == 4
    assert diag["delivered_batches"] == 2  # units, not row batches


def test_stack_drop_last_semantics(stack_ds):
    # 64 rows / batch 8 = 8 batches; K=3 -> 2 full stacks + 2 leftover batches
    def run(drop_last):
        reader = make_reader(stack_ds, shuffle_row_groups=False,
                             reader_pool_type="serial",
                             schema_fields=["idx"])
        with JaxDataLoader(reader, batch_size=8, fields=["idx"],
                           stack_batches=3, drop_last=drop_last) as loader:
            return list(loader)

    dropped = run(True)
    assert len(dropped) == 2  # short final stack dropped, like a partial batch
    assert all("_valid_rows" not in u for u in dropped)

    padded = run(False)
    assert len(padded) == 3
    tail = padded[-1]
    assert tail["idx"].shape == (3, 8)  # static signature even when short
    np.testing.assert_array_equal(np.asarray(tail["_valid_rows"]), [8, 8, 0])
    assert np.asarray(tail["idx"])[2].tolist() == [0] * 8  # zero-pad step
    flat = [int(v) for u in padded
            for k, step in enumerate(np.asarray(u["idx"]))
            for v in step[:int(np.asarray(u.get("_valid_rows", [8] * 3))[k])]]
    assert flat == list(range(N_ROWS))


def test_stack_on_mesh_sharding_and_mask(stack_ds):
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    # 64 rows / global batch 24 -> 2 full + 1 partial(16); K=2 -> the second
    # stack is [partial(16), missing]
    reader = make_reader(stack_ds, shuffle_row_groups=False,
                         schema_fields=["idx", "vec"])
    with JaxDataLoader(reader, batch_size=24, mesh=mesh, stack_batches=2,
                       fields=["idx", "vec"], drop_last=False,
                       shardings={"idx": P("data"), "vec": P("data")},
                       valid_mask_field="mask") as loader:
        units = list(loader)
    assert len(units) == 2
    u = units[0]
    assert u["idx"].shape == (2, 24)
    assert u["vec"].shape == (2, 24, 6)
    assert u["mask"].shape == (2, 24)
    assert u["idx"].sharding.spec == P(None, "data")
    shard_shapes = {s.data.shape for s in u["vec"].addressable_shards}
    assert shard_shapes == {(2, 3, 6)}  # stack axis unsharded, 24/8 rows each
    assert np.asarray(u["mask"]).tolist() == [[1.0] * 24] * 2
    tail = units[1]
    np.testing.assert_array_equal(np.asarray(tail["_valid_rows"]), [16, 0])
    mask = np.asarray(tail["mask"])
    assert mask[0].tolist() == [1.0] * 16 + [0.0] * 8
    assert mask[1].tolist() == [0.0] * 24
    assert np.asarray(tail["idx"])[0, 16:].tolist() == [0] * 8
    # every real row delivered exactly once
    ids = []
    for u in units:
        m = np.asarray(u["mask"]) > 0
        ids.extend(np.asarray(u["idx"])[m].tolist())
    assert sorted(ids) == list(range(N_ROWS))


def test_stack_host_fields_and_transform(stack_ds):
    calls = []

    def xform(cols):
        calls.append(len(cols["idx"]))  # runs per BATCH, before stacking
        cols = dict(cols)
        cols["idx"] = cols["idx"] * 2
        return cols

    reader = make_reader(stack_ds, shuffle_row_groups=False,
                         reader_pool_type="serial",
                         schema_fields=["idx", "tag"])
    with JaxDataLoader(reader, batch_size=8, fields=["idx"],
                       host_fields=["tag"], stack_batches=2,
                       transform_fn=xform) as loader:
        units = list(loader)
    assert all(n == 8 for n in calls) and len(calls) == 8
    u = units[0]
    assert u["tag"].shape == (2, 8) and u["tag"].dtype == object
    assert u["tag"][0, 0] == "t0" and u["tag"][1, 0] == "t8"
    assert np.asarray(u["idx"])[0].tolist() == [2 * i for i in range(8)]


def test_stack_lax_scan_consumer(stack_ds):
    """The delivered (K, B, ...) unit drives lax.scan directly - the whole
    point of the capability."""
    reader = make_reader(stack_ds, shuffle_row_groups=False,
                         schema_fields=["idx", "vec"])

    @jax.jit
    def scan_sum(vecs):           # (K, B, 6) -> scalar via K scanned steps
        def body(carry, x):
            return carry + x.sum(), None
        total, _ = jax.lax.scan(body, jnp.float32(0), vecs)
        return total

    total = 0.0
    expect = 0.0
    with JaxDataLoader(reader, batch_size=8, fields=["idx", "vec"],
                       stack_batches=4) as loader:
        for u in loader:
            total += float(scan_sum(u["vec"]))
            expect += float(np.asarray(u["vec"]).sum())
    assert total == pytest.approx(expect, rel=1e-5)


def test_stack_drain_exact_resume(tmp_path):
    """drain()/state_dict() at stack granularity: zero re-read rows."""
    # enough rowgroups that the in-flight window (queues + the accumulating
    # stack group) cannot swallow the whole dataset before quiesce
    url = str(tmp_path / "drain_ds")
    rng = np.random.default_rng(1)
    n_rows = 128
    write_dataset(url, SCHEMA,
                  [{"idx": i, "vec": rng.standard_normal(6).astype(np.float32),
                    "tag": f"t{i}"} for i in range(n_rows)],
                  row_group_size_rows=2)
    seen = []
    with make_batch_reader(url, reader_pool_type="thread", workers_count=2,
                           results_queue_size=2, shuffle_seed=7,
                           num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=4, stack_batches=2,
                           fields=["idx", "vec"], drop_last=False) as loader:
            it = iter(loader)
            u = next(it)
            seen.extend(np.asarray(u["idx"]).reshape(-1).tolist())
            for u in loader.drain():
                valid = np.asarray(u.get("_valid_rows", [4, 4]))
                for k, step in enumerate(np.asarray(u["idx"])):
                    seen.extend(step[:valid[k]].tolist())
            state = loader.state_dict()
    assert state["reader"]["ordinal_exact"]
    assert state["stack_batches"] == 2

    resumed = []
    with make_batch_reader(url, reader_pool_type="thread", workers_count=2,
                           shuffle_seed=7, num_epochs=1,
                           resume_from=state["reader"]) as r:
        with JaxDataLoader(r, batch_size=4, stack_batches=2,
                           fields=["idx", "vec"], drop_last=False) as loader:
            for u in loader:
                valid = np.asarray(u.get("_valid_rows", [4, 4]))
                for k, step in enumerate(np.asarray(u["idx"])):
                    resumed.extend(step[:valid[k]].tolist())
    counts = collections.Counter(seen + resumed)
    assert sorted(counts) == list(range(n_rows)), "rows lost"
    assert max(counts.values()) == 1, "rows re-read: cursor was not exact"
    assert resumed, "drain consumed everything; resume proved nothing"


def test_stack_drain_multihost_alignment(stack_ds):
    """Short hosts pad with zero STACKS whose '_valid_rows' is a (K,) zero
    array and whose mask is all-zero - the pod-safe drain contract at stack
    granularity."""
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(stack_ds, shuffle_row_groups=False,
                           num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=16, mesh=mesh, stack_batches=2,
                           drop_last=False, fields=["idx", "vec"],
                           shardings={"idx": P("data"), "vec": P("data")},
                           valid_mask_field="mask") as loader:
            it = iter(loader)
            next(it)
            drained = list(loader.drain(
                all_gather_counts=lambda mine: [mine, mine + 2]))
    pads = drained[-2:]
    for pad in pads:
        np.testing.assert_array_equal(np.asarray(pad["_valid_rows"]), [0, 0])
        assert pad["idx"].shape == (2, 16)
        assert pad["idx"].sharding.spec == P(None, "data")
        assert np.asarray(pad["mask"]).sum() == 0
        assert np.asarray(pad["vec"]).sum() == 0


def test_stack_validation_errors(stack_ds):
    reader = make_reader(stack_ds, schema_fields=["idx", "vec"])
    try:
        with pytest.raises(PetastormTpuError, match="stack_batches must be"):
            JaxDataLoader(reader, batch_size=8, stack_batches=0)
        with pytest.raises(PetastormTpuError, match="device_shuffle_capacity"):
            JaxDataLoader(reader, batch_size=8, fields=["idx", "vec"],
                          stack_batches=2, device_shuffle_capacity=4)
        with pytest.raises(PetastormTpuError, match="multi-bucket"):
            JaxDataLoader(reader, batch_size=8, fields=["vec"],
                          stack_batches=2,
                          pad_shapes={"vec": [(6,), (8,)]})
    finally:
        reader.stop()
        reader.join()


# -- hybrid on-chip decode under stacking -------------------------------------
# cv2/native guards live INSIDE the fixture and tests: a module-level
# importorskip would silently skip the eight core stack tests above, which
# need neither

from petastorm_tpu.native import image as native_image  # noqa: E402

needs_native = pytest.mark.skipif(not native_image.available(),
                                  reason="native image library unavailable")


@pytest.fixture(scope="module")
def jpeg_ds(tmp_path_factory):
    pytest.importorskip("cv2")
    from petastorm_tpu.codecs import CompressedImageCodec

    from tests.test_jpeg_hybrid import _smooth_rgb

    schema = Schema("StackJpeg", [
        Field("idx", np.int64),
        Field("image", np.uint8, (24, 32, 3),
              CompressedImageCodec("jpeg", quality=92)),
    ])
    url = str(tmp_path_factory.mktemp("stack_jpeg") / "ds")
    write_dataset(url, schema,
                  [{"idx": i, "image": _smooth_rgb(24, 32, seed=i)}
                   for i in range(32)],
                  row_group_size_rows=8)
    return url


@needs_native
def test_stack_device_decode_uniform(jpeg_ds):
    """decode_placement='device' + stack_batches: the K batches' coefficient
    planes ship as one (K, B, ...) transfer and decode in one on-chip call;
    pixels match the host decode."""
    from tests.test_jpeg_hybrid import _cv2_decode, _encode, _smooth_rgb

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(jpeg_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh, stack_batches=2,
                           fields=["idx", "image"],
                           shardings={"idx": P("data"),
                                      "image": P("data")}) as loader:
            units = list(loader)
    assert len(units) == 2
    u = units[0]
    assert u["image"].shape == (2, 8, 24, 32, 3)
    assert u["image"].sharding.spec == P(None, "data")
    got = {}
    for u in units:
        idxs = np.asarray(u["idx"])
        imgs = np.asarray(u["image"])
        for k in range(idxs.shape[0]):
            for j, i in enumerate(idxs[k]):
                got[int(i)] = imgs[k, j]
    assert sorted(got) == list(range(32))
    for i in (0, 9, 31):
        ref = _cv2_decode(_encode(_smooth_rgb(24, 32, seed=i), quality=92))
        diff = np.abs(ref.astype(int) - got[i].astype(int))
        assert diff.max() <= 6 and diff.mean() < 1.0, f"idx {i}"


@needs_native
def test_stack_device_decode_partial_tail(jpeg_ds):
    """Short final stack + partial rows with on-chip decode: zero-gray pad
    rows, per-step '_valid_rows', mask marks exactly the real rows."""
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    # 32 rows / batch 24 -> full + partial(8); K=2 -> one stack [24, 8]
    with make_batch_reader(jpeg_ds, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"}) as r:
        with JaxDataLoader(r, batch_size=24, mesh=mesh, stack_batches=2,
                           drop_last=False, fields=["idx", "image"],
                           shardings={"idx": P("data"), "image": P("data")},
                           valid_mask_field="mask") as loader:
            units = list(loader)
    assert len(units) == 1
    u = units[0]
    np.testing.assert_array_equal(np.asarray(u["_valid_rows"]), [24, 8])
    mask = np.asarray(u["mask"])
    assert mask[0].tolist() == [1.0] * 24
    assert mask[1].tolist() == [1.0] * 8 + [0.0] * 16
    ids = np.asarray(u["idx"])[mask > 0]
    assert sorted(ids.tolist()) == list(range(32))


@needs_native
def test_stack_mixed_decode(tmp_path):
    """decode_placement='device-mixed' + stack_batches: the K batches' cells
    decode as one flat bucket pass, reshape to (K, B, ...), scatter."""
    from petastorm_tpu.codecs import CompressedImageCodec

    from tests.test_jpeg_hybrid import _cv2_decode, _encode, _smooth_rgb

    geometries = [(16, 24), (24, 16)]
    target = (24, 24, 3)
    schema = Schema("StackMixed", [
        Field("idx", np.int64),
        Field("image", np.uint8, (None, None, 3),
              CompressedImageCodec("jpeg", quality=92)),
    ])
    url = str(tmp_path / "ds")
    write_dataset(url, schema,
                  [{"idx": i,
                    "image": _smooth_rgb(*geometries[i % 2], seed=i)}
                   for i in range(16)],
                  row_group_size_rows=4)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(url, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device-mixed"}) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh, stack_batches=2,
                           fields=["idx", "image"],
                           pad_shapes={"image": target},
                           shardings={"idx": P("data"),
                                      "image": P("data")}) as loader:
            units = list(loader)
    assert len(units) == 1
    u = units[0]
    assert u["image"].shape == (2, 8) + target
    assert u["image"].sharding.spec == P(None, "data")
    idxs, imgs = np.asarray(u["idx"]), np.asarray(u["image"])
    for k in range(2):
        for j, i in enumerate(idxs[k]):
            h, w = geometries[int(i) % 2]
            ref = _cv2_decode(_encode(_smooth_rgb(h, w, seed=int(i)),
                                      quality=92))
            diff = np.abs(ref.astype(int) - imgs[k, j, :h, :w].astype(int))
            assert diff.max() <= 6 and diff.mean() < 1.0, f"idx {int(i)}"
