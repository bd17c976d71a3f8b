"""Real multi-process validation of the multi-host data plane.

Every multi-host claim in this framework ultimately rests on three JAX
primitives: the ``jax.distributed.initialize`` process topology,
``jax.make_array_from_process_local_data`` global-batch assembly, and the
``multihost_utils.process_allgather`` drain alignment.  On a single machine
those paths are normally only *simulated* - one process pretending to be many
hosts, which is exactly how the reference simulates sharding too
(petastorm/tests/test_end_to_end.py:454 runs every "worker" in-process).
This module executes them for REAL: it launches N separate OS processes on
the CPU backend (Gloo collectives over localhost), each owning a disjoint
subset of one shared device mesh, and drives

* sharded reading    - ``shard_options_from_jax()`` resolved per process
* global assembly    - every batch built with ``jax.process_count() > 1``
* collective steps   - a jitted masked global mean per step (replicated
                       output realized on every host)
* drain alignment    - the REAL ``process_allgather`` branch of
                       ``JaxDataLoader.drain`` (no injected counts), with
                       hosts configured to buffer deliberately unequal
                       amounts so the zero-pad path must fire
* valid-mask safety  - pads carry a zero ``valid_mask_field`` column and the
                       collective runs on EVERY drained step (the no-hang
                       contract; see JaxDataLoader.drain docs)
* elastic resume     - a second launch under a DIFFERENT process count
                       resumes from ``elastic_resume()`` of the saved cursors
* dp x tp meshes     - ``run_mesh2d_check``: 2-D mesh delivery with the data
                       axis crossing processes and tensor parallelism inside
                       each, one jitted reduction over both axes
* coordinated writes - ``run_distributed_write_check``: the default
                       ``sync_global_devices`` barrier path of
                       ``distributed_write_dataset`` (never reachable from
                       single-process tests), geometry sidecar merge, and
                       exact all-host readback
* context parallel   - ``run_context_parallel_check``: sequence-sharded
                       delivery plus ring attention (ppermute) and Ulysses
                       (all_to_all) over a mesh SPANNING the processes,
                       checked against a full-attention reference
* shuffled + stacked - ``run_shuffled_check``: SEEDED shuffled sharded
                       reading with ``stack_batches=2`` delivery and stacked
                       drain, at 4 processes: all hosts must realize the
                       identical permutation (replicated all-gather of every
                       unit's ids), the masked multiset must equal the
                       dataset, the order must match the locally recomputed
                       seeded plan, and the pod shuffle-quality
                       rank-correlation bound runs on the REAL-process rows
* mixed decode       - ``run_mixed_check``: ``device-mixed`` jpeg decode on
                       a mesh spanning processes - host-local bucket decode,
                       global-array scatter, pixels all-gathered and checked
                       bit-identical across hosts and against a host decode

and verifies, in the launching process, that the rows every process observed
reconstruct the single-process ground truth row for row, and that phase-1
consumption plus phase-2 resume cover the dataset exactly once.

Usage (also wired into the driver dry-run and the test suite)::

    from petastorm_tpu.parallel.selfcheck import run_selfcheck
    report = run_selfcheck(num_processes=2, devices_per_process=2)
    assert report["ok"], report["failures"]

or from a shell::

    python -m petastorm_tpu.parallel.selfcheck --num-processes 2
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import re
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

import numpy as np

MASK_FIELD = "mask"
#: head count of the context-parallel check's attention (Ulysses runs only
#: when this divides the device count; ring has no such constraint)
_CP_HEADS = 4
#: vocab/hidden of the 2-D mesh check's embedding computation
_M2D_VOCAB = 32
_M2D_HIDDEN = 16
_ID = "id"
_VALUE = "value"
_VALUE_DIM = 4


def _value_for_ids(ids):
    import numpy as np

    ids = np.asarray(ids, dtype=np.float32)
    return np.stack([ids * 0.5, ids - 3.0, ids % 7.0, ids * 0.25],
                    axis=-1).astype(np.float32)


# ---------------------------------------------------------------------------
# worker side (runs in each spawned process)
# ---------------------------------------------------------------------------

def _worker_main(args) -> None:
    # the parent puts every child on the CPU platform (JAX_PLATFORMS=cpu)
    import jax

    jax.distributed.initialize(coordinator_address=args.coordinator,
                               num_processes=args.num_processes,
                               process_id=args.process_id)
    if args.phase == "pipeline":
        _worker_pipeline(args)
    elif args.phase == "resume":
        _worker_resume(args)
    elif args.phase == "cp":
        _worker_cp(args)
    elif args.phase == "write":
        _worker_write(args)
    elif args.phase == "mesh2d":
        _worker_mesh2d(args)
    elif args.phase == "shuffled":
        _worker_shuffled(args)
    elif args.phase == "mixed":
        _worker_mixed(args)
    else:
        raise ValueError(f"unknown phase {args.phase!r}")


def _worker_pipeline(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.parallel.mesh import shard_options_from_jax
    from petastorm_tpu.reader import make_reader

    pid = jax.process_index()
    assert jax.process_count() == args.num_processes, (
        jax.process_count(), args.num_processes)
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("data",))
    cur, count = shard_options_from_jax()
    local_rows = args.global_batch * len(jax.local_devices()) // len(devices)

    # DELIBERATELY asymmetric buffering: the higher-ranked process holds a much
    # deeper in-flight window, so at drain time the hosts have unequal batch
    # counts and the alignment pad path MUST fire on the shallow host(s)
    # workers_count=1 pins delivery to plan order (multi-worker pools deliver
    # in completion order, legitimately nondeterministic) so the launcher can
    # assert row-for-row equality against its own single-process read
    reader = make_reader(args.dataset, cur_shard=cur, shard_count=count,
                         shuffle_row_groups=False, num_epochs=1,
                         workers_count=1, results_queue_size=2 + 8 * pid)
    rep = NamedSharding(mesh, P())
    masked_mean = jax.jit(
        lambda v, m: (v.sum(axis=1) * m).sum() / jnp.maximum(m.sum(), 1.0),
        out_shardings=rep)

    batches: List[Dict] = []

    def record(batch, kind):
        entry = {"kind": kind, "shards": [], "mask": [],
                 "valid_rows": int(batch.get("_valid_rows", -1)),
                 "values_match": True}
        for sh in batch[_ID].addressable_shards:
            sl = sh.index[0]
            ids = np.asarray(sh.data).ravel()
            entry["shards"].append({"start": int(sl.start or 0),
                                    "stop": int(sl.stop),
                                    "ids": ids.astype(int).tolist()})
        for sh in batch[_VALUE].addressable_shards:
            sl = sh.index[0]
            ids = next(s["ids"] for s in entry["shards"]
                       if s["start"] == int(sl.start or 0))
            vals = np.asarray(sh.data)
            if entry["valid_rows"] != 0 and not np.allclose(
                    vals, _value_for_ids(ids)):
                entry["values_match"] = False
        for sh in batch[MASK_FIELD].addressable_shards:
            sl = sh.index[0]
            entry["mask"].append({"start": int(sl.start or 0),
                                  "vals": np.asarray(sh.data).ravel().tolist()})
        batches.append(entry)

    steps = 0
    means: List[float] = []
    with JaxDataLoader(reader, batch_size=args.global_batch, mesh=mesh,
                       shardings={_ID: P("data"), _VALUE: P("data")},
                       drop_last=False, prefetch=2 + 6 * pid,
                       valid_mask_field=MASK_FIELD) as loader:
        it = iter(loader)
        first = next(it)
        means.append(float(masked_mean(first[_VALUE], first[MASK_FIELD])))
        steps += 1
        record(first, "consumed")
        time.sleep(args.settle)  # let every host's pipeline buffer to capacity

        drained_real = pad_count = 0
        for b in loader.drain():  # REAL process_allgather alignment
            # the contract under pod collectives: run EVERY drained step (the
            # mask zeroes pad rows out of the loss); branching on the
            # host-local '_valid_rows' here would hang the other process
            means.append(float(masked_mean(b[_VALUE], b[MASK_FIELD])))
            steps += 1
            if b.get("_valid_rows", local_rows) == 0:
                pad_count += 1
                record(b, "drain_pad")
            else:
                drained_real += 1
                record(b, "drain_real")
        state = loader.state_dict()["reader"]

    real_all = multihost_utils.process_allgather(
        np.asarray([drained_real], np.int32)).ravel()
    drain_steps_all = multihost_utils.process_allgather(
        np.asarray([drained_real + pad_count], np.int32)).ravel()
    steps_all = multihost_utils.process_allgather(
        np.asarray([steps], np.int32)).ravel()
    assert len(set(drain_steps_all.tolist())) == 1, (
        f"drain alignment broken: per-host drain step counts {drain_steps_all}")
    assert len(set(steps_all.tolist())) == 1, (
        f"collective step counts diverged: {steps_all}")
    assert state.get("ordinal_exact"), state

    with open(os.path.join(args.out, f"state_{pid}.pkl"), "wb") as f:
        pickle.dump(state, f)
    report = {
        "process_id": pid,
        "process_count": jax.process_count(),
        "n_devices": len(devices),
        "n_local_devices": len(jax.local_devices()),
        "local_rows": local_rows,
        "cur_shard": cur,
        "shard_count": count,
        "drained_real": int(drained_real),
        "pad_count": int(pad_count),
        "real_all": real_all.tolist(),
        "drain_steps_all": drain_steps_all.tolist(),
        "steps_all": steps_all.tolist(),
        "means": means,
        "batches": batches,
    }
    with open(os.path.join(args.out, f"worker_{pid}.json"), "w") as f:
        json.dump(report, f)


def _worker_cp(args) -> None:
    """Context-parallel data plane + attention collectives across REAL
    process boundaries: sequence-sharded loader delivery (every host reads
    every row, materializes only its sequence slice), then ring attention
    (ppermute K/V rotation) and Ulysses (all_to_all head/sequence reshard)
    run over a mesh spanning both processes and must match a local
    full-attention reference on the replicated data."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.ops.ring_attention import ring_attention
    from petastorm_tpu.ops.ulysses import ulysses_attention
    from petastorm_tpu.reader import make_reader

    pid = jax.process_index()
    devices = jax.devices()
    n_dev = len(devices)
    mesh = Mesh(np.asarray(devices).reshape(1, n_dev), ("data", "seq"))
    rep = NamedSharding(mesh, P())

    reader = make_reader(args.dataset, shuffle_row_groups=False, num_epochs=1,
                         workers_count=1)
    with JaxDataLoader(reader, batch_size=args.global_batch, mesh=mesh,
                       shardings={_ID: P("data"),
                                  "x": P("data", "seq")}) as loader:
        batch = next(iter(loader))
        x = batch["x"]  # (B, S, D) global; sequence sharded across processes
    B, S, D = x.shape
    H = _CP_HEADS
    dh = D // H

    to_bhsd = jax.jit(
        lambda t: t.reshape(B, S, H, dh).transpose(0, 2, 1, 3),
        out_shardings=NamedSharding(mesh, P(None, None, "seq", None)))
    qkv = to_bhsd(x)
    out_ring = ring_attention(qkv, qkv, qkv, mesh=mesh, causal=True)
    replicate = jax.jit(lambda t: t, out_shardings=rep)
    ring_rep = np.asarray(replicate(out_ring))

    # local reference from the REPLICATED input (float64 softmax)
    x_rep = np.asarray(replicate(x)).astype(np.float64)
    q = x_rep.reshape(B, S, H, dh).transpose(0, 2, 1, 3)
    scores = np.einsum("bhqd,bhkd->bhqk", q, q) / (dh ** 0.5)
    mask = np.tril(np.ones((S, S), bool))
    scores = np.where(mask, scores, -np.inf)
    w = np.exp(scores - scores.max(axis=-1, keepdims=True))
    w /= w.sum(axis=-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", w, q)

    err_ring = float(np.max(np.abs(ring_rep - ref)))
    assert err_ring < 2e-4, f"ring attention diverged: max err {err_ring}"
    err_uly = None
    if H % n_dev == 0:
        uly_rep = np.asarray(replicate(
            ulysses_attention(qkv, qkv, qkv, mesh=mesh, causal=True)))
        err_uly = float(np.max(np.abs(uly_rep - ref)))
        assert err_uly < 2e-4, f"ulysses diverged: max err {err_uly}"

    with open(os.path.join(args.out, f"cp_{pid}.json"), "w") as f:
        json.dump({"process_id": pid, "process_count": jax.process_count(),
                   "err_ring": err_ring, "err_uly": err_uly,
                   "ring_sum": float(ring_rep.sum()),
                   "shape": [int(B), int(S), int(D)]}, f)


def run_context_parallel_check(num_processes: int = 2,
                               devices_per_process: int = 2,
                               seq: int = 32, dim: int = 32,
                               global_batch: int = 2,
                               timeout: float = 240.0,
                               workdir: Optional[str] = None) -> Dict:
    """Ring + Ulysses attention over sequence-sharded delivery in REAL
    separate processes; see ``_worker_cp``.  Returns {"ok", "failures", ...}.
    """
    import tempfile

    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.schema import Field, Schema

    n_dev = num_processes * devices_per_process
    assert seq % n_dev == 0, (
        f"seq ({seq}) must divide over the {n_dev}-device mesh")
    assert dim % _CP_HEADS == 0, (
        f"dim ({dim}) must be divisible by the head count ({_CP_HEADS})")
    workdir = workdir or tempfile.mkdtemp(prefix="petastorm_tpu_cpcheck_")
    os.makedirs(workdir, exist_ok=True)
    dataset = os.path.join(workdir, f"cp_s{seq}_d{dim}_b{global_batch}")
    if not os.path.exists(dataset):
        rng = np.random.default_rng(11)
        schema = Schema("CpCheck", [
            Field(_ID, np.int32),
            Field("x", np.float32, (seq, dim)),
        ])
        write_dataset(dataset, schema,
                      [{_ID: np.int32(i),
                        "x": rng.standard_normal((seq, dim)).astype(np.float32)}
                       for i in range(global_batch)],
                      row_group_size_rows=global_batch)
    report, workers = _launch_and_collect(
        "cp", num_processes, devices_per_process, dataset, workdir, timeout,
        ["--global-batch", str(global_batch)])
    if workers is None:
        return report
    sums = {w["ring_sum"] for w in workers}
    if len(sums) != 1:
        report["failures"].append(
            f"hosts realized different ring outputs: {sums}")
    report["err_ring"] = max(w["err_ring"] for w in workers)
    uly = [w["err_uly"] for w in workers if w["err_uly"] is not None]
    # Ulysses runs only when the head count divides the device count; ring
    # alone still proves the cross-process collective path
    report["err_uly"] = max(uly) if uly else None
    report["ok"] = not report["failures"]
    return report


def _worker_shuffled(args) -> None:
    """SHUFFLED sharded reading + STACKED delivery + stacked drain across
    real process boundaries (with the stack-mode drain): every host reads its
    shard with the same seeded rowgroup permutation, units arrive as
    (K, G, ...) stacks, and the per-unit global id/mask arrays are REPLICATED
    (a real cross-process all-gather) so the launcher can assert all hosts
    realized the identical permutation."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.parallel.mesh import shard_options_from_jax
    from petastorm_tpu.reader import make_reader

    pid = jax.process_index()
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("data",))
    rep = NamedSharding(mesh, P())
    cur, count = shard_options_from_jax()
    reader = make_reader(args.dataset, cur_shard=cur, shard_count=count,
                         shuffle_row_groups=True, shuffle_seed=args.seed,
                         num_epochs=1, workers_count=1)
    replicate = jax.jit(lambda t: t, out_shardings=rep)
    masked_mean = jax.jit(
        lambda v, m: (v.sum(axis=-1) * m).sum() / jnp.maximum(m.sum(), 1.0),
        out_shardings=rep)
    units: List[Dict] = []
    means: List[float] = []

    def record(u):
        # the collective runs on EVERY unit (incl. drain pads) - the stacked
        # no-hang contract - and the replicated ids ARE the cross-host proof
        means.append(float(masked_mean(u[_VALUE], u[MASK_FIELD])))
        units.append({
            "ids": np.asarray(replicate(u[_ID])).astype(int).tolist(),
            "mask": np.asarray(replicate(u[MASK_FIELD])).tolist()})

    with JaxDataLoader(reader, batch_size=args.global_batch, mesh=mesh,
                       stack_batches=2, drop_last=False,
                       shardings={_ID: P("data"), _VALUE: P("data")},
                       valid_mask_field=MASK_FIELD) as loader:
        it = iter(loader)
        record(next(it))
        for u in loader.drain():  # stacked drain over real process_allgather
            record(u)
    with open(os.path.join(args.out, f"shuffled_{pid}.json"), "w") as f:
        json.dump({"process_id": pid, "process_count": jax.process_count(),
                   "units": units, "means": means}, f)


def run_shuffled_check(num_processes: int = 4, devices_per_process: int = 2,
                       global_batch: int = 8, n_batches: int = 16,
                       seed: int = 9, timeout: float = 300.0,
                       workdir: Optional[str] = None) -> Dict:
    """Seeded SHUFFLED reading with stacked delivery over real processes;
    see ``_worker_shuffled``.  Asserts (a) every host realized the identical
    global unit sequence (permutation agreement), (b) the masked multiset
    equals the dataset exactly, (c) the delivered order matches the seeded
    plan's lockstep interleave recomputed locally, (d) the shuffle-quality
    rank-correlation bound holds on rows collected from REAL processes
    (previously only simulated in-process,
    tests/test_weighted_and_shuffle_quality.py)."""
    import tempfile

    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.test_util.shuffling_analysis import rank_correlation

    assert global_batch % num_processes == 0
    assert n_batches % 2 == 0, "stack_batches=2 needs an even batch count"
    local_rows = global_batch // num_processes
    total = n_batches * global_batch
    workdir = workdir or tempfile.mkdtemp(prefix="petastorm_tpu_shufcheck_")
    os.makedirs(workdir, exist_ok=True)
    dataset = os.path.join(
        workdir, f"shuf_gb{global_batch}_nb{n_batches}_np{num_processes}")
    if not os.path.exists(dataset):
        schema = Schema("ShufCheck", [
            Field(_ID, np.int32),
            Field(_VALUE, np.float32, (_VALUE_DIM,)),
        ])
        write_dataset(dataset, schema,
                      [{_ID: np.int32(i), _VALUE: _value_for_ids([i])[0]}
                       for i in range(total)],
                      row_group_size_rows=local_rows)
    report, workers = _launch_and_collect(
        "shuffled", num_processes, devices_per_process, dataset, workdir,
        timeout, ["--global-batch", str(global_batch), "--seed", str(seed)])
    if workers is None:
        return report
    failures = report["failures"]
    seqs = {json.dumps(w["units"]) for w in workers}
    if len(seqs) != 1:
        failures.append("hosts realized DIFFERENT global unit sequences -"
                        " the seeded permutation diverged across processes")
    if len({tuple(w["means"]) for w in workers}) != 1:
        failures.append("hosts realized different collective results")
    flat: List[int] = []
    for u in workers[0]["units"]:
        for ids_step, mask_step in zip(u["ids"], u["mask"]):
            flat.extend(i for i, m in zip(ids_step, mask_step) if m > 0)
    if sorted(flat) != list(range(total)):
        dup = len(flat) - len(set(flat))
        failures.append(f"shuffled multiset broken: {len(flat)} rows,"
                        f" {dup} duplicated (want each of {total} once)")
    if flat == sorted(flat):
        failures.append("delivered order is the written order - nothing"
                        " shuffled")
    rho = abs(rank_correlation(np.asarray(flat))) if flat else 1.0
    report["rho_global"] = round(float(rho), 4)
    if rho > 0.5:
        failures.append(f"pod shuffle quality: global |rho|={rho:.3f} > 0.5")

    # the seeded plan is a pure function of (seed, shard): recompute each
    # shard's stream locally and interleave in lockstep - the pod's delivered
    # order must match it exactly (determinism across real processes)
    per_shard: List[List[int]] = []
    for p in range(num_processes):
        r = make_reader(dataset, cur_shard=p, shard_count=num_processes,
                        shuffle_row_groups=True, shuffle_seed=seed,
                        num_epochs=1, workers_count=1)
        ids: List[int] = []
        try:
            for cb in r.iter_batches():
                ids.extend(np.asarray(cb.columns[_ID]).astype(int).tolist())
        finally:
            r.stop()
            r.join()
        per_shard.append(ids)
    expect: List[int] = []
    for t in range(n_batches):
        for p in range(num_processes):
            expect.extend(per_shard[p][t * local_rows:(t + 1) * local_rows])
    if flat and flat != expect:
        failures.append("delivered order != seeded plan interleave (plan"
                        " determinism broken across real processes)")
    report["units"] = len(workers[0]["units"])
    report["ok"] = not failures
    return report


_MIXED_GEOMS = ((16, 24), (24, 16))
_MIXED_TARGET = (24, 24, 3)


def _worker_mixed(args) -> None:
    """'device-mixed' jpeg decode over a mesh SPANNING real processes: each
    host entropy+bucket-decodes only ITS batch rows (host-local, per-geometry
    compiles), delivery declares one global array, and the REPLICATING
    all-gather proves rows decoded on host A arrive bit-identical on host B."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.parallel.mesh import shard_options_from_jax
    from petastorm_tpu.reader import make_batch_reader

    pid = jax.process_index()
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("data",))
    rep = NamedSharding(mesh, P())
    cur, count = shard_options_from_jax()
    reader = make_batch_reader(args.dataset, cur_shard=cur, shard_count=count,
                               shuffle_row_groups=False, num_epochs=1,
                               workers_count=1,
                               decode_placement={"image": "device-mixed"})
    replicate = jax.jit(lambda t: t, out_shardings=rep)
    got: Dict[int, np.ndarray] = {}
    with JaxDataLoader(reader, batch_size=args.global_batch, mesh=mesh,
                       fields=[_ID, "image"],
                       pad_shapes={"image": _MIXED_TARGET},
                       shardings={_ID: P("data"), "image": P("data")}) as loader:
        for b in loader:
            ids = np.asarray(replicate(b[_ID])).astype(int)
            imgs = np.asarray(replicate(b["image"]))
            for k, i in enumerate(ids):
                got[int(i)] = imgs[k]
        diag = loader.diagnostics
    order = sorted(got)
    np.save(os.path.join(args.out, f"mixed_{pid}.npy"),
            np.stack([got[i] for i in order]))
    with open(os.path.join(args.out, f"mixed_{pid}.json"), "w") as f:
        json.dump({"process_id": pid, "process_count": jax.process_count(),
                   "ids": order,
                   "geometries": diag.get("mixed_decode_geometries", {}),
                   "declared": diag.get("declared_geometries", {})}, f)


def run_mixed_check(num_processes: int = 2, devices_per_process: int = 4,
                    global_batch: int = 8, n_rows: int = 16,
                    timeout: float = 300.0,
                    workdir: Optional[str] = None) -> Dict:
    """'device-mixed' decode across real process boundaries; see
    ``_worker_mixed``.  The launcher generates a 2-geometry jpeg dataset,
    every worker decodes only its shard's rows on its own host, and the
    check compares (a) all hosts' replicated pixel arrays bit-for-bit and
    (b) every image against the launcher's own HOST decode of the stored
    bytes within the hybrid-decode tolerance."""
    import tempfile

    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.test_util.synthetic import synthetic_rgb_image

    workdir = workdir or tempfile.mkdtemp(prefix="petastorm_tpu_mixcheck_")
    os.makedirs(workdir, exist_ok=True)
    dataset = os.path.join(workdir, f"mixed_n{n_rows}_gb{global_batch}")
    if not os.path.exists(dataset):
        schema = Schema("MixCheck", [
            Field(_ID, np.int32),
            Field("image", np.uint8, (None, None, 3),
                  CompressedImageCodec("jpeg", quality=92)),
        ])
        write_dataset(dataset, schema,
                      [{_ID: np.int32(i),
                        "image": synthetic_rgb_image(
                            i, *_MIXED_GEOMS[i % len(_MIXED_GEOMS)], noise=0)}
                       for i in range(n_rows)],
                      row_group_size_rows=global_batch // num_processes)
    report, workers = _launch_and_collect(
        "mixed", num_processes, devices_per_process, dataset, workdir,
        timeout, ["--global-batch", str(global_batch)])
    if workers is None:
        return report
    failures = report["failures"]
    arrays = [np.load(os.path.join(workdir, f"mixed_{w['process_id']}.npy"))
              for w in workers]
    if any(w["ids"] != list(range(n_rows)) for w in workers):
        failures.append("a host did not observe every row id")
    for w, a in zip(workers[1:], arrays[1:]):
        if not np.array_equal(arrays[0], a):
            failures.append(
                f"host {w['process_id']}: replicated decoded pixels differ"
                " from host 0 (cross-process scatter broke)")
            break
    geom_counts = {json.dumps(w["geometries"]) for w in workers}
    report["geometries_per_host"] = [w["geometries"] for w in workers]
    if any(w["geometries"].get("image", 0) > len(_MIXED_GEOMS)
           for w in workers):
        failures.append("a host compiled more decode geometries than the"
                        f" dataset holds: {sorted(geom_counts)}")
    if failures:
        # a host missed rows or pixels diverged: arrays[0] may be short, so
        # the per-row reference comparison below would IndexError instead of
        # returning the structured report
        report["ok"] = False
        return report
    # reference: the launcher's own host decode of the stored bytes
    ref: Dict[int, np.ndarray] = {}
    with make_batch_reader(dataset, shuffle_row_groups=False,
                           num_epochs=1, workers_count=1) as r:
        for cb in r.iter_batches():
            for i, img in zip(np.asarray(cb.columns[_ID]).astype(int),
                              cb.columns["image"]):
                ref[int(i)] = np.asarray(img)
    max_err, mean_err = 0.0, 0.0
    for i in range(n_rows):
        h, w_ = ref[i].shape[:2]
        dev = arrays[0][i]
        diff = np.abs(ref[i].astype(int) - dev[:h, :w_].astype(int))
        max_err = max(max_err, float(diff.max()))
        mean_err = max(mean_err, float(diff.mean()))
        if diff.max() > 6 or diff.mean() >= 1.0:
            failures.append(f"row {i}: device-mixed pixels off by"
                            f" max {diff.max()} / mean {diff.mean():.2f}"
                            " vs host decode")
            break
        if dev[h:].any() or dev[:, w_:].any():
            failures.append(f"row {i}: pad region not zero")
            break
    report["max_pixel_err"] = max_err
    report["mean_pixel_err"] = round(mean_err, 3)
    report["rows"] = n_rows
    report["ok"] = not failures
    return report


def _worker_mesh2d(args) -> None:
    """2-D mesh delivery with the DATA axis crossing the process boundary
    and the MODEL axis inside each process (dp x tp, the standard pod
    layout): sequence axis of 'tokens' sharded over 'model', batch over
    'data', then one jitted computation with a tp-sharded weight whose mean
    reduces over BOTH axes - psum inside each process, cross-process data
    reduction over Gloo - must equal a local numpy reference and agree
    bit-for-bit across hosts."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.parallel.mesh import shard_options_from_jax
    from petastorm_tpu.reader import make_reader

    pid = jax.process_index()
    devices = jax.devices()
    per = len(jax.local_devices())
    mesh = Mesh(np.asarray(devices).reshape(len(devices) // per, per),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    cur, count = shard_options_from_jax()
    reader = make_reader(args.dataset, cur_shard=cur, shard_count=count,
                         shuffle_row_groups=False, num_epochs=1,
                         workers_count=1)
    with JaxDataLoader(reader, batch_size=args.global_batch, mesh=mesh,
                       shardings={_ID: P("data"),
                                  "tokens": P("data", "model")}) as loader:
        batch = next(iter(loader))
        ids_g, tokens_g = batch[_ID], batch["tokens"]

    rng = np.random.default_rng(5)
    emb_np = rng.standard_normal((_M2D_VOCAB, _M2D_HIDDEN)).astype(np.float32)
    w_np = rng.standard_normal((_M2D_HIDDEN, _M2D_HIDDEN)).astype(np.float32)
    w_sharding = NamedSharding(mesh, P(None, "model"))  # tp-sharded weight
    W = jax.make_array_from_callback(w_np.shape, w_sharding,
                                     lambda idx: w_np[idx])
    emb = jax.make_array_from_callback(emb_np.shape, rep,
                                       lambda idx: emb_np[idx])
    loss_fn = jax.jit(
        lambda t, w, e: jnp.mean(jnp.einsum("bsh,hk->bsk", e[t], w)),
        out_shardings=rep)
    loss = float(loss_fn(tokens_g, W, emb))

    replicate = jax.jit(lambda t: t, out_shardings=rep)
    ids = np.asarray(replicate(ids_g)).astype(int)
    tokens = np.asarray(replicate(tokens_g))
    S = tokens.shape[1]
    expected = (ids[:, None] * 7 + np.arange(S)[None, :]) % _M2D_VOCAB
    assert np.array_equal(tokens, expected), "2-D delivery scrambled rows"
    ref = float(np.mean(np.einsum("bsh,hk->bsk", emb_np[tokens], w_np)))
    err = abs(loss - ref) / max(abs(ref), 1e-9)
    assert err < 1e-5, f"dp x tp collective diverged: {loss} vs {ref}"

    # every addressable token shard must live inside this process's data row
    lo = pid * (args.global_batch // jax.process_count())
    hi = lo + args.global_batch // jax.process_count()
    for sh in tokens_g.addressable_shards:
        b_sl = sh.index[0]
        # a replicated-delivery regression shows up as slice(None) bounds -
        # which a coalescing check would wave through on process 0
        assert b_sl.start is not None and b_sl.stop is not None, sh.index
        assert lo <= b_sl.start and b_sl.stop <= hi, sh.index
    with open(os.path.join(args.out, f"mesh2d_{pid}.json"), "w") as f:
        json.dump({"process_id": pid, "loss": loss, "ref": ref,
                   "mesh": {k: int(v) for k, v in mesh.shape.items()}}, f)


def _launch_and_collect(phase: str, num_processes: int,
                        devices_per_process: int, dataset: str, workdir: str,
                        timeout: float, extra: Optional[List[str]] = None,
                        result_prefix: Optional[str] = None):
    """Shared launcher boilerplate: spawn the workers, wait, load their
    result JSONs.  Returns ``(report, workers)``; ``workers`` is None when
    the launch failed (``report['failures']``/``'timeout'`` say why)."""
    report: Dict = {"ok": False, "timeout": False, "environment": False,
                    "failures": [], "workdir": workdir}
    logs: List[str] = []
    report["logs"] = logs
    error = _launch(phase, num_processes, devices_per_process, dataset,
                    workdir, timeout, logs, extra)
    if error:
        report["failures"].append(error)
        report["timeout"] = "timed out" in error
        report["environment"] = "environment-bound" in error
        return report, None
    workers = []
    prefix = result_prefix or phase
    for pid in range(num_processes):
        with open(os.path.join(workdir, f"{prefix}_{pid}.json")) as f:
            workers.append(json.load(f))
    return report, workers


def run_mesh2d_check(num_processes: int = 2, devices_per_process: int = 2,
                     global_batch: int = 8, seq: int = 8,
                     timeout: float = 240.0,
                     workdir: Optional[str] = None) -> Dict:
    """dp x tp delivery + collectives over a 2-D mesh whose data axis crosses
    real process boundaries; see ``_worker_mesh2d``."""
    import tempfile

    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.schema import Field, Schema

    assert global_batch % num_processes == 0 and \
        global_batch >= num_processes, (
        f"global_batch ({global_batch}) must divide over the data axis"
        f" ({num_processes} processes)")
    assert seq % devices_per_process == 0, (
        f"seq ({seq}) must divide over the model axis"
        f" ({devices_per_process} devices/process)")
    workdir = workdir or tempfile.mkdtemp(prefix="petastorm_tpu_m2dcheck_")
    os.makedirs(workdir, exist_ok=True)
    # every parameter that shapes the written file is in the cache name, so
    # a reused workdir can never serve a stale-geometry dataset
    dataset = os.path.join(
        workdir, f"m2d_b{global_batch}_s{seq}_np{num_processes}")
    if not os.path.exists(dataset):
        schema = Schema("Mesh2d", [
            Field(_ID, np.int32),
            Field("tokens", np.int32, (seq,)),
        ])
        total = global_batch * 4
        write_dataset(dataset, schema,
                      [{_ID: np.int32(i),
                        "tokens": ((i * 7 + np.arange(seq)) % _M2D_VOCAB
                                   ).astype(np.int32)}
                       for i in range(total)],
                      row_group_size_rows=global_batch // num_processes)
    report, workers = _launch_and_collect(
        "mesh2d", num_processes, devices_per_process, dataset, workdir,
        timeout, ["--global-batch", str(global_batch)])
    if workers is None:
        return report
    losses = {w["loss"] for w in workers}
    if len(losses) != 1:
        report["failures"].append(f"hosts realized different losses: {losses}")
    report["loss"] = workers[0]["loss"]
    report["mesh"] = workers[0]["mesh"]
    report["ok"] = not report["failures"]
    return report


def _worker_write(args) -> None:
    """Coordinated multi-host dataset write with the DEFAULT sync path: real
    ``multihost_utils.sync_global_devices`` barriers over Gloo (the in-repo
    tests simulate hosts with a threading.Barrier; this executes the actual
    collective), host-0 metadata stamp incl. merged geometry sidecars, then
    every host reads the stamped dataset back and checksums it."""
    import jax
    import numpy as np

    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.parallel.write import distributed_write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema

    pid = jax.process_index()
    count = jax.process_count()
    schema = Schema("MpWrite", [
        Field(_ID, np.int32),
        Field("image", np.uint8, (None, None, 3),
              CompressedImageCodec("png")),  # lossless: exact readback
    ])
    total = args.global_batch * 8
    rng = np.random.default_rng(7)
    all_rows = [{_ID: np.int32(i),
                 "image": rng.integers(0, 255, ((16, 24) if i % 2 else (24, 16))
                                       + (3,), dtype=np.uint8)}
                for i in range(total)]
    url = os.path.join(args.out, "mp_written_ds")
    # DEFAULT coordination: process_index/count and sync_fn come from the JAX
    # distributed runtime - the code path single-process tests cannot reach
    files = distributed_write_dataset(url, schema, all_rows[pid::count],
                                      row_group_size_rows=4)
    ids = []
    with make_batch_reader(url, num_epochs=1, workers_count=1) as r:
        declared = r.declared_geometries
        for cb in r.iter_batches():
            ids.extend(np.asarray(cb.columns[_ID]).astype(int).tolist())
    assert sorted(ids) == list(range(total)), (len(ids), total)
    assert sorted(declared["image"]) == [(16, 24, 3), (24, 16, 3)], declared
    with open(os.path.join(args.out, f"write_{pid}.json"), "w") as f:
        json.dump({"process_id": pid, "files": len(files),
                   "rows_read": len(ids),
                   "geometries": sorted(declared["image"])}, f)


def run_distributed_write_check(num_processes: int = 2,
                                global_batch: int = 8,
                                timeout: float = 240.0,
                                workdir: Optional[str] = None) -> Dict:
    """Multi-host coordinated write through the REAL sync_global_devices
    barriers; see ``_worker_write``.  Returns {"ok", "failures", ...}."""
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="petastorm_tpu_wrcheck_")
    os.makedirs(workdir, exist_ok=True)
    report, workers = _launch_and_collect(
        "write", num_processes, 1, "unused", workdir, timeout,
        ["--global-batch", str(global_batch)])
    if workers is None:
        return report
    report["rows_read"] = workers[0]["rows_read"]
    report["files_per_host"] = [w["files"] for w in workers]
    if any(w["rows_read"] != workers[0]["rows_read"] for w in workers):
        report["failures"].append("hosts read back different row counts")
    if any(w["files"] == 0 for w in workers):
        report["failures"].append("a host wrote no part files")
    report["ok"] = not report["failures"]
    return report


def _worker_resume(args) -> None:
    import jax

    from petastorm_tpu.parallel.mesh import shard_options_from_jax
    from petastorm_tpu.reader import elastic_resume, make_reader

    pid = jax.process_index()
    with open(args.resume_states, "rb") as f:
        states = pickle.load(f)
    token = elastic_resume(states)
    cur, count = shard_options_from_jax()
    reader = make_reader(args.dataset, cur_shard=cur, shard_count=count,
                         shuffle_row_groups=False, num_epochs=1,
                         workers_count=1, resume_from=token)
    ids: List[int] = []
    try:
        for cb in reader.iter_batches():
            ids.extend(np.asarray(cb.columns[_ID]).astype(int).tolist())
    finally:
        reader.stop()
        reader.join()
    with open(os.path.join(args.out, f"resume_{pid}.json"), "w") as f:
        json.dump({"process_id": pid, "process_count": jax.process_count(),
                   "ids": ids}, f)


# ---------------------------------------------------------------------------
# launcher side
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(devices_per_process: int) -> Dict[str, str]:
    import petastorm_tpu

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count="
                        f"{devices_per_process}").strip()
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(petastorm_tpu.__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    return env


#: worker-log markers of failures that are properties of the RUNTIME, not
#: the data plane: this jax build simply cannot run the check here.  A
#: worker exit matching one is reported "environment-bound" so callers
#: (tests) can skip rather than fail - same contract as launcher timeouts.
_ENV_BOUND_MARKERS = (
    # jax 0.4.x CPU backend has no cross-process collective implementation
    "Multiprocess computations aren't implemented on the CPU backend",
    "Unable to initialize backend",
)


def _environment_bound_reason(log_path: str) -> Optional[str]:
    """The matching environment-bound marker line from a failed worker's
    log, or None (a real failure)."""
    try:
        with open(log_path, errors="replace") as f:
            tail = f.read()[-20000:]
    except OSError:
        return None
    for marker in _ENV_BOUND_MARKERS:
        if marker in tail:
            return marker
    return None


def _launch(phase: str, num_processes: int, devices_per_process: int,
            dataset: str, out: str, timeout: float, logs: List[str],
            extra: Optional[List[str]] = None) -> Optional[str]:
    """Spawn one worker per process, wait, return an error string or None."""
    port = _free_port()
    env = _worker_env(devices_per_process)
    procs = []
    for pid in range(num_processes):
        log_path = os.path.join(out, f"{phase}_{pid}.log")
        logs.append(log_path)
        log = open(log_path, "w")
        cmd = [sys.executable, "-m", "petastorm_tpu.parallel.selfcheck",
               "--worker", "--phase", phase,
               "--process-id", str(pid),
               "--num-processes", str(num_processes),
               "--coordinator", f"127.0.0.1:{port}",
               "--dataset", dataset, "--out", out] + (extra or [])
        procs.append((subprocess.Popen(cmd, env=env, stdout=log, stderr=log),
                      log))
    deadline = time.monotonic() + timeout
    error = None
    try:
        for proc, _ in procs:
            left = max(0.1, deadline - time.monotonic())
            try:
                code = proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                error = (f"{phase}: timed out after {timeout:.0f}s"
                         " (collective hang or machine too slow)")
                break
            if code != 0 and error is None:
                error = f"{phase}: worker exited with code {code}"
    finally:
        for proc, log in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            log.close()
    if error and "timed out" not in error:
        # classify runtime-capability exits (e.g. a jax build whose CPU
        # backend has no cross-process collectives) so callers can skip
        for pid in range(num_processes):
            reason = _environment_bound_reason(
                os.path.join(out, f"{phase}_{pid}.log"))
            if reason is not None:
                return (f"{phase}: environment-bound: {reason}"
                        f" (worker {pid})")
    return error


def run_selfcheck(num_processes: int = 2,
                  devices_per_process: int = 2,
                  global_batch: int = 8,
                  n_batches: int = 28,
                  resume_processes: Optional[int] = 3,
                  settle: float = 1.5,
                  timeout: float = 240.0,
                  workdir: Optional[str] = None) -> Dict:
    """Run the multi-process data-plane check; return a report dict.

    ``report["ok"]`` is True when every invariant held; ``report["failures"]``
    lists what broke (``report["timeout"]`` marks an environment-style failure
    the caller may choose to skip on rather than fail).
    """
    import tempfile

    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_reader
    from petastorm_tpu.schema import Field, Schema

    assert global_batch % (num_processes * devices_per_process) == 0, (
        "global_batch must divide evenly over the device mesh")
    local_rows = global_batch // num_processes
    total_rows = n_batches * global_batch

    workdir = workdir or tempfile.mkdtemp(prefix="petastorm_tpu_selfcheck_")
    os.makedirs(workdir, exist_ok=True)
    # params-keyed name: a reused workdir with different batch/process
    # geometry must regenerate, not misverify against a stale dataset
    dataset = os.path.join(
        workdir, f"ds_gb{global_batch}_nb{n_batches}_np{num_processes}")
    schema = Schema("SelfCheck", [
        Field(_ID, np.int32),
        Field(_VALUE, np.float32, (_VALUE_DIM,)),
    ])
    if not os.path.exists(dataset):
        write_dataset(dataset, schema,
                      [{_ID: np.int32(i), _VALUE: _value_for_ids([i])[0]}
                       for i in range(total_rows)],
                      row_group_size_rows=local_rows)

    report: Dict = {"ok": False, "timeout": False, "environment": False,
                    "failures": [],
                    "workdir": workdir, "num_processes": num_processes,
                    "devices_per_process": devices_per_process,
                    "global_batch": global_batch, "n_batches": n_batches}
    failures = report["failures"]
    logs: List[str] = []
    report["logs"] = logs

    # up to two pipeline attempts: drained-count inequality (which forces the
    # pad path) comes from engineered buffering asymmetry plus a settle
    # sleep, and a slow/contended box can still even the counts out - that
    # is a property of the box, not a data-plane failure, so retry once with
    # a longer settle and report `pad_exercised` rather than failing
    report["notes"] = notes = []
    workers: List[Dict] = []
    attempt_settle = settle
    for attempt in range(2):
        error = _launch("pipeline", num_processes, devices_per_process,
                        dataset, workdir, timeout, logs,
                        ["--global-batch", str(global_batch),
                         "--settle", str(attempt_settle)])
        if error:
            failures.append(error)
            report["timeout"] = "timed out" in error
            report["environment"] = "environment-bound" in error
            return report
        workers = []
        for pid in range(num_processes):
            with open(os.path.join(workdir, f"worker_{pid}.json")) as f:
                workers.append(json.load(f))
        if len(set(workers[0]["real_all"])) > 1 or attempt == 1:
            break
        notes.append(f"attempt {attempt + 1}: hosts drained equal counts"
                     f" {workers[0]['real_all']}; retrying with settle"
                     f" {attempt_settle * 2}")
        attempt_settle *= 2
    report["pad_exercised"] = len(set(workers[0]["real_all"])) > 1

    # ground truth: what each shard yields when read in THIS process
    def shard_ids(shard: int, count: int) -> List[int]:
        r = make_reader(dataset, cur_shard=shard, shard_count=count,
                        shuffle_row_groups=False, num_epochs=1,
                        workers_count=1)
        out: List[int] = []
        try:
            for cb in r.iter_batches():
                out.extend(np.asarray(cb.columns[_ID]).astype(int).tolist())
        finally:
            r.stop()
            r.join()
        return out

    expected_shards = [shard_ids(p, num_processes)
                       for p in range(num_processes)]

    # -- per-worker checks ---------------------------------------------------
    consumed: List[int] = []
    for w in workers:
        pid = w["process_id"]
        if w["process_count"] != num_processes:
            failures.append(f"worker {pid}: process_count {w['process_count']}")
        if w["n_devices"] != num_processes * devices_per_process:
            failures.append(f"worker {pid}: saw {w['n_devices']} devices")
        exp = expected_shards[pid]
        real = [b for b in w["batches"] if b["kind"] != "drain_pad"]
        pads = [b for b in w["batches"] if b["kind"] == "drain_pad"]
        lo = pid * local_rows
        for k, b in enumerate(real):
            shards = sorted(b["shards"], key=lambda s: s["start"])
            got = [i for s in shards for i in s["ids"]]
            want = exp[k * local_rows:(k + 1) * local_rows]
            if got != want:
                failures.append(
                    f"worker {pid} batch {k}: rows {got} != expected {want}"
                    " (global assembly placed the wrong data)")
                break
            starts = [s["start"] for s in shards]
            if starts[0] != lo or shards[-1]["stop"] != lo + local_rows:
                failures.append(
                    f"worker {pid} batch {k}: local shards cover"
                    f" [{starts[0]}, {shards[-1]['stop']}) but this process"
                    f" owns [{lo}, {lo + local_rows})")
                break
            if not b["values_match"]:
                failures.append(f"worker {pid} batch {k}: value column does"
                                " not match f(id)")
                break
            mask_vals = [v for m in sorted(b["mask"], key=lambda s: s["start"])
                         for v in m["vals"]]
            if mask_vals != [1.0] * local_rows:
                failures.append(f"worker {pid} batch {k}: real batch mask"
                                f" {mask_vals}")
                break
        for b in pads:
            mask_vals = [v for m in b["mask"] for v in m["vals"]]
            if any(v != 0.0 for v in mask_vals):
                failures.append(f"worker {pid}: pad batch has nonzero mask")
            if b["valid_rows"] != 0:
                failures.append(f"worker {pid}: pad batch valid_rows"
                                f" {b['valid_rows']}")
        consumed.extend(exp[:len(real) * local_rows])
        if len(set(w["drain_steps_all"])) != 1:
            failures.append(f"worker {pid}: unaligned drain steps"
                            f" {w['drain_steps_all']}")
        if any(not np.isfinite(m) for m in w["means"]):
            failures.append(f"worker {pid}: non-finite collective result")

    # -- cross-worker checks -------------------------------------------------
    real_counts = workers[0]["real_all"]
    report["drained_real_per_process"] = real_counts
    report["pad_counts"] = [w["pad_count"] for w in workers]
    if not report["pad_exercised"]:
        notes.append(
            "hosts drained equal counts on both attempts - the pad path was"
            " not exercised this run (slow box, not a data-plane failure)")
    elif sum(report["pad_counts"]) == 0:
        failures.append("hosts drained unequal counts but no alignment pads"
                        " were emitted")
    means = [tuple(w["means"]) for w in workers]
    if len(set(means)) != 1:
        failures.append("hosts realized different collective results:"
                        f" {means} (replicated output must agree)")

    # -- phase 2: elastic resume under a different process count -------------
    if resume_processes:
        states = []
        for pid in range(num_processes):
            with open(os.path.join(workdir, f"state_{pid}.pkl"), "rb") as f:
                states.append(pickle.load(f))
        with open(os.path.join(workdir, "states.pkl"), "wb") as f:
            pickle.dump(states, f)
        error = _launch("resume", resume_processes, 1, dataset, workdir,
                        timeout, logs,
                        ["--resume-states",
                         os.path.join(workdir, "states.pkl")])
        if error:
            failures.append(error)
            report["timeout"] = report["timeout"] or "timed out" in error
            report["environment"] = (report.get("environment", False)
                                     or "environment-bound" in error)
            return report
        resumed: List[int] = []
        for pid in range(resume_processes):
            with open(os.path.join(workdir, f"resume_{pid}.json")) as f:
                resumed.extend(json.load(f)["ids"])
        report["consumed_rows"] = len(consumed)
        report["resumed_rows"] = len(resumed)
        both = sorted(consumed + resumed)
        if both != list(range(total_rows)):
            dup = len(both) - len(set(both))
            missing = sorted(set(range(total_rows)) - set(both))[:10]
            failures.append(
                f"resume not exact: {dup} duplicated rows, first missing"
                f" {missing} ({len(consumed)} consumed + {len(resumed)}"
                f" resumed of {total_rows})")

    report["ok"] = not failures
    return report


def _main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--worker", action="store_true",
                        help="internal: run as a spawned worker process")
    parser.add_argument("--phase", default="pipeline",
                        choices=["pipeline", "resume", "cp", "write",
                                 "mesh2d", "shuffled", "mixed"])
    parser.add_argument("--process-id", type=int, default=0)
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--coordinator", default=None)
    parser.add_argument("--dataset", default=None)
    parser.add_argument("--out", default=None)
    parser.add_argument("--global-batch", type=int, default=8)
    parser.add_argument("--seed", type=int, default=9)
    parser.add_argument("--settle", type=float, default=1.5)
    parser.add_argument("--resume-states", default=None)
    parser.add_argument("--devices-per-process", type=int, default=2)
    parser.add_argument("--resume-processes", type=int, default=3)
    parser.add_argument("--timeout", type=float, default=240.0)
    args = parser.parse_args()
    if args.worker:
        _worker_main(args)
        return 0
    # launcher mode: --phase picks which check to run (the 'resume' phase is
    # part of the pipeline check, not standalone)
    if args.phase == "pipeline":
        report = run_selfcheck(num_processes=args.num_processes,
                               devices_per_process=args.devices_per_process,
                               global_batch=args.global_batch,
                               resume_processes=args.resume_processes,
                               settle=args.settle, timeout=args.timeout)
    elif args.phase == "cp":
        report = run_context_parallel_check(
            num_processes=args.num_processes,
            devices_per_process=args.devices_per_process,
            timeout=args.timeout)
    elif args.phase == "write":
        report = run_distributed_write_check(
            num_processes=args.num_processes, timeout=args.timeout)
    elif args.phase == "mesh2d":
        report = run_mesh2d_check(
            num_processes=args.num_processes,
            devices_per_process=args.devices_per_process,
            timeout=args.timeout)
    elif args.phase == "shuffled":
        report = run_shuffled_check(
            num_processes=args.num_processes,
            devices_per_process=args.devices_per_process,
            seed=args.seed, timeout=args.timeout)
    elif args.phase == "mixed":
        report = run_mixed_check(
            num_processes=args.num_processes,
            devices_per_process=args.devices_per_process,
            timeout=args.timeout)
    else:
        print(f"--phase {args.phase} is not a standalone check (it runs"
              " inside the pipeline check)")
        return 2
    print(json.dumps(report, indent=2))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(_main())
