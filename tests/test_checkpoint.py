"""Checkpoint/resume: loader cursor paired with orbax training checkpoints.

Reference gap (SURVEY.md section 5): petastorm cannot resume an epoch; the TPU
build pairs a deterministic data cursor with the model state in one checkpoint.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("orbax.checkpoint")

from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.jax import (JaxDataLoader, make_checkpoint_manager,
                               restore_checkpoint, resume_reader_kwargs,
                               save_checkpoint)
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.schema import Field, Schema

SCHEMA = Schema("Ckpt", [Field("id", np.int64), Field("x", np.float32, (4,))])
N_ROWS, RG_ROWS = 64, 8  # 8 rowgroups


@pytest.fixture(scope="module")
def ds(tmp_path_factory):
    url = str(tmp_path_factory.mktemp("ckpt") / "ds")
    rng = np.random.default_rng(0)
    write_dataset(url, SCHEMA,
                  [{"id": i, "x": rng.standard_normal(4).astype(np.float32)}
                   for i in range(N_ROWS)],
                  row_group_size_rows=RG_ROWS)
    return url


def test_loader_state_dict_shape(ds):
    reader = make_batch_reader(ds, shuffle_row_groups=False, num_epochs=1)
    with JaxDataLoader(reader, batch_size=8) as loader:
        it = iter(loader)
        next(it)
        state = loader.state_dict()
    assert state["delivered_batches"] == 1
    assert state["global_batch"] == 8
    assert "position" in state["reader"]


def test_resume_continues_at_cursor(ds):
    """After consuming the whole first epoch of a 2-epoch reader, resuming
    from the saved cursor replays exactly epoch 2 (exact at boundaries)."""
    reader = make_reader(ds, shuffle_row_groups=False, num_epochs=2,
                         workers_count=1)
    seen = []
    with JaxDataLoader(reader, batch_size=8, fields=["id", "x"]) as loader:
        for batch in loader:
            seen.extend(int(v) for v in np.asarray(batch["id"]))
            if len(seen) == N_ROWS:  # exactly one epoch delivered
                state = loader.state_dict()
                break
    assert sorted(seen) == list(range(N_ROWS))

    # the cursor may sit anywhere inside epoch 2's prefetched prefix; resuming
    # must yield exactly the plan suffix from that position, once
    pos = state["reader"]["position"]
    items_per_epoch = state["reader"]["items_per_epoch"]
    assert items_per_epoch == N_ROWS // RG_ROWS
    reader2 = make_reader(ds, shuffle_row_groups=False, num_epochs=2,
                          workers_count=1, resume_from={"position": pos})
    with JaxDataLoader(reader2, batch_size=8, fields=["id", "x"],
                       drop_last=False) as loader2:
        resumed = [int(v) for b in loader2 for v in np.asarray(b["id"])]
    expected = []
    for item in range(pos, 2 * items_per_epoch):
        rg = item % items_per_epoch
        expected.extend(range(rg * RG_ROWS, (rg + 1) * RG_ROWS))
    assert resumed == expected


def test_orbax_composite_roundtrip(ds, tmp_path):
    """Model state + loader cursor live in one orbax checkpoint."""
    train_state = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                   "step": jnp.asarray(3)}
    reader = make_batch_reader(ds, shuffle_row_groups=False, num_epochs=1)
    with JaxDataLoader(reader, batch_size=8) as loader:
        it = iter(loader)
        next(it)
        next(it)
        mngr = make_checkpoint_manager(str(tmp_path / "ckpts"), max_to_keep=2)
        assert save_checkpoint(mngr, step=3, train_state=train_state,
                               loader_or_state=loader)
        mngr.wait_until_finished()

    template = jax.tree.map(np.zeros_like, train_state)
    restored_state, loader_state = restore_checkpoint(mngr, template)
    np.testing.assert_array_equal(np.asarray(restored_state["w"]),
                                  np.asarray(train_state["w"]))
    assert loader_state["delivered_batches"] == 2
    kwargs = resume_reader_kwargs(loader_state)
    assert kwargs["resume_from"]["position"] == loader_state["reader"]["position"]

    # the resume kwargs plug straight into a new reader
    r = make_batch_reader(ds, shuffle_row_groups=False, num_epochs=1, **kwargs)
    with JaxDataLoader(r, batch_size=8, drop_last=False) as loader2:
        remaining = sum(int(next(iter(b.values())).shape[0]) for b in loader2)
    assert remaining <= N_ROWS
    mngr.close()


def test_state_dict_requires_real_reader():
    from petastorm_tpu.errors import PetastormTpuError
    from petastorm_tpu.test_util.reader_mock import ReaderMock

    mock = ReaderMock(SCHEMA.view(["x"]), batch_size=4, num_batches=2)
    with JaxDataLoader(mock, batch_size=4) as loader:
        with pytest.raises(PetastormTpuError, match="state_dict"):
            loader.state_dict()


def test_drain_to_cursor_exact_resume(ds):
    """drain() + state_dict() is an exact cursor - resume
    re-reads ZERO rows, with a thread pool and the HBM device shuffle buffer
    both active."""
    import collections

    # enough rowgroups that the in-flight window cannot swallow the whole
    # dataset before quiesce: with a seeded reader deterministic delivery is
    # auto-armed and its ventilation RELEASE WINDOW (~2x the executor's
    # in-flight capacity, ~52 items here) structurally caps how far the
    # pipeline runs ahead of the release point - 256 items keeps
    # "drain stopped mid-stream" guaranteed, not a timing race (128 items
    # could fully ventilate before quiesce on a fast run)
    url = ds + "_drain"
    import os
    if not os.path.exists(url):
        rng = np.random.default_rng(1)
        write_dataset(url, SCHEMA,
                      [{"id": i, "x": rng.standard_normal(4).astype(np.float32)}
                       for i in range(512)],
                      row_group_size_rows=2)
    ds = url
    n_rows = 512

    seen = []
    with make_batch_reader(ds, reader_pool_type="thread", workers_count=4,
                           results_queue_size=4,
                           shuffle_seed=5, num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=8, drop_last=False,
                           device_shuffle_capacity=3,
                           device_shuffle_seed=0) as loader:
            it = iter(loader)
            for _ in range(2):  # a couple of training steps
                b = next(it)
                seen.extend(int(v) for v in np.asarray(b["id"]))
            drained = list(loader.drain())  # preemption: flush in-flight work
            for b in drained:
                seen.extend(int(v) for v in np.asarray(b["id"]))
            state = loader.state_dict()
    assert state["reader"]["ordinal_exact"]

    resumed = []
    with make_batch_reader(ds, reader_pool_type="thread", workers_count=4,
                           shuffle_seed=5, num_epochs=1,
                           resume_from=state["reader"]) as r:
        with JaxDataLoader(r, batch_size=8, drop_last=False) as loader:
            for b in loader:
                resumed.extend(int(v) for v in np.asarray(b["id"]))

    counts = collections.Counter(seen + resumed)
    assert sorted(counts) == list(range(n_rows)), "rows lost"
    assert max(counts.values()) == 1, "rows re-read: cursor was not exact"
    assert len(resumed) > 0  # the drain really stopped mid-stream


def test_drain_after_exhaustion_is_empty(ds):
    with make_batch_reader(ds, num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=8) as loader:
            n = sum(1 for _ in loader)
            assert n == 8
            assert list(loader.drain()) == []


def test_drain_with_saturated_pipeline_no_deadlock(ds):
    """The preemption case: prefetch ran far ahead, every bounded queue is
    full, the ventilator is blocked mid-put.  drain() must cancel that put
    and flush cleanly instead of deadlocking (the put is withdrawn, so the
    cursor stays exact)."""
    import collections
    import os
    import time

    url = ds + "_saturated"
    if not os.path.exists(url):
        rng = np.random.default_rng(2)
        write_dataset(url, SCHEMA,
                      [{"id": i, "x": rng.standard_normal(4).astype(np.float32)}
                       for i in range(256)],
                      row_group_size_rows=2)

    seen = []
    with make_batch_reader(url, reader_pool_type="thread", workers_count=4,
                           results_queue_size=4, shuffle_seed=3,
                           num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=8, drop_last=False) as loader:
            it = iter(loader)
            seen.extend(int(v) for v in np.asarray(next(it)["id"]))
            time.sleep(1.5)  # let every bounded stage fill to capacity
            t0 = time.perf_counter()
            for b in loader.drain():
                seen.extend(int(v) for v in np.asarray(b["id"]))
            assert time.perf_counter() - t0 < 30, "drain deadlocked"
            state = loader.state_dict()
    assert state["reader"]["ordinal_exact"]

    resumed = []
    with make_batch_reader(url, reader_pool_type="thread", workers_count=4,
                           shuffle_seed=3, num_epochs=1,
                           resume_from=state["reader"]) as r:
        with JaxDataLoader(r, batch_size=8, drop_last=False) as loader:
            for b in loader:
                resumed.extend(int(v) for v in np.asarray(b["id"]))
    counts = collections.Counter(seen + resumed)
    assert sorted(counts) == list(range(256)) and max(counts.values()) == 1
    assert resumed  # saturation really left work for the resume


def test_drain_multihost_alignment_pads_short_hosts(ds):
    """With a mesh and a pod, hosts drain unequal counts; the shorter host
    must pad with zero '_valid_rows' batches so collective steps align."""
    import jax
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(ds, reader_pool_type="thread", shuffle_seed=1,
                           num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings=PartitionSpec("data"),
                           drop_last=False) as loader:
            it = iter(loader)
            next(it)
            # pretend a peer host drained 3 more batches than we will
            drained = list(loader.drain(
                all_gather_counts=lambda mine: [mine, mine + 3]))
    real = [b for b in drained if b.get("_valid_rows", b["id"].shape[0]) != 0]
    pads = [b for b in drained if b.get("_valid_rows", -1) == 0]
    assert len(pads) == 3
    for p in pads:
        assert p["id"].shape == real[-1]["id"].shape
        assert str(p["id"].sharding.spec) == str(PartitionSpec("data"))
        assert np.asarray(p["id"]).sum() == 0


def test_drain_zero_batch_host_synthesizes_pads(ds):
    """A host that drained ZERO batches while a peer drained some must still
    yield synthesized pad batches (shapes from the schema) so the pod steps in
    lockstep - raising here would hang the peers mid-collective."""
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(ds, reader_pool_type="serial", num_epochs=1,
                           shuffle_row_groups=False) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings=PartitionSpec("data"),
                           drop_last=False) as loader:
            for _ in loader:  # exhaust: nothing left in flight to drain
                pass
            drained = list(loader.drain(
                all_gather_counts=lambda mine: [mine, mine + 2]))
    assert len(drained) == 2
    for p in drained:
        assert p["_valid_rows"] == 0
        assert p["id"].shape == (8,)
        assert p["x"].shape == (8, 4)
        assert str(p["x"].sharding.spec) == str(PartitionSpec("data"))
        assert np.asarray(p["x"]).sum() == 0


def test_drain_zero_batch_host_without_any_emitted_batch(ds):
    """Zero-batch alignment must work even when NO batch was ever emitted on
    this host (empty placement cache): shapes come from the schema."""
    from jax.sharding import Mesh, PartitionSpec

    from petastorm_tpu.predicates import in_lambda

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    nothing = in_lambda(["id"], lambda cols: np.zeros(len(cols["id"]), bool),
                        vectorized=True)
    with make_batch_reader(ds, reader_pool_type="serial", num_epochs=1,
                           predicate=nothing, shuffle_row_groups=False) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings=PartitionSpec("data"),
                           drop_last=False) as loader:
            drained = list(loader.drain(
                all_gather_counts=lambda mine: [mine, 1]))
    assert len(drained) == 1
    (p,) = drained
    assert p["_valid_rows"] == 0
    assert p["id"].shape == (8,) and p["x"].shape == (8, 4)
    assert np.asarray(p["x"]).sum() == 0


def test_drain_pads_carry_zero_valid_mask(ds):
    """Drain-alignment pads must zero the valid_mask_field column so a
    collective consumer that weights by the mask (the pod-safe pattern;
    branching on host-local '_valid_rows' would diverge control flow) sees
    the pad rows contribute nothing."""
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with make_batch_reader(ds, reader_pool_type="thread", shuffle_seed=1,
                           num_epochs=1) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings=PartitionSpec("data"),
                           drop_last=False, valid_mask_field="mask") as loader:
            it = iter(loader)
            first = next(it)
            assert np.asarray(first["mask"]).tolist() == [1.0] * 8
            drained = list(loader.drain(
                all_gather_counts=lambda mine: [mine, mine + 3]))
    pads = [b for b in drained if b.get("_valid_rows", -1) == 0]
    assert len(pads) == 3
    for p in pads:
        assert np.asarray(p["mask"]).tolist() == [0.0] * 8


def test_drain_zero_batch_host_synthesizes_mask(ds):
    """The zero-batch-host synthesized pads (no template batch, no placement
    cache) must still include the valid_mask_field column, zeroed."""
    from jax.sharding import Mesh, PartitionSpec

    from petastorm_tpu.predicates import in_lambda

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    nothing = in_lambda(["id"], lambda cols: np.zeros(len(cols["id"]), bool),
                        vectorized=True)
    with make_batch_reader(ds, reader_pool_type="serial", num_epochs=1,
                           predicate=nothing, shuffle_row_groups=False) as r:
        with JaxDataLoader(r, batch_size=8, mesh=mesh,
                           shardings=PartitionSpec("data"),
                           drop_last=False, valid_mask_field="mask") as loader:
            drained = list(loader.drain(
                all_gather_counts=lambda mine: [mine, 1]))
    (p,) = drained
    assert p["_valid_rows"] == 0
    assert p["mask"].shape == (8,)
    assert np.asarray(p["mask"]).tolist() == [0.0] * 8
