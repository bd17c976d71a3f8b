"""REAL multi-process execution of the multi-host data plane.

Everything else in this suite simulates hosts in one process (the reference
does the same: petastorm/tests/test_end_to_end.py:454).  These tests launch
genuinely separate OS processes via ``jax.distributed`` on the CPU backend
(Gloo collectives over localhost) and prove, with ``process_count > 1``:

* ``shard_options_from_jax`` sharded reading per process
* ``jax.make_array_from_process_local_data`` global-batch assembly - the
  launcher reconstructs every global batch from each process's addressable
  shards and matches it row-for-row against a single-process read
* ``JaxDataLoader.drain`` through the REAL ``multihost_utils.process_allgather``
  branch (no injected counts), with deliberately unequal host buffering so the
  zero-pad alignment path must fire
* the ``valid_mask_field`` no-hang contract: a collective step runs on EVERY
  drained step, pads carrying a zero mask, and all hosts realize identical
  replicated results
* ``elastic_resume`` across a process-count change (2 -> 3): phase-1
  consumption plus phase-2 resume cover the dataset exactly once

Skipped (not failed) on launcher timeout: collective hangs and glacial shared
CI boxes are indistinguishable from here, and a hang IS the failure mode the
drain alignment exists to prevent - the selfcheck's own asserts catch real
misalignment well before the timeout.
"""

import pytest

from petastorm_tpu.parallel.selfcheck import run_selfcheck


def _skip_if_unrunnable(report, what):
    """Skip (never fail) on the two environment-style launch outcomes: a
    launcher timeout (hang vs glacial box, indistinguishable from here) and
    an environment-bound worker exit (this jax build cannot run the check at
    all, e.g. a CPU backend without cross-process collectives - selfcheck
    classifies worker logs against known markers)."""
    if report["timeout"]:
        pytest.skip(f"{what} timed out: {report['failures']}")
    if report.get("environment"):
        pytest.skip(f"environment-bound: {report['failures']}")



def test_multiprocess_data_plane(tmp_path):
    report = run_selfcheck(num_processes=2, devices_per_process=2,
                           global_batch=8, n_batches=28, resume_processes=3,
                           workdir=str(tmp_path), timeout=300.0)
    _skip_if_unrunnable(report, "multi-process selfcheck")
    assert report["ok"], report["failures"]
    # both phases moved real data
    assert report["consumed_rows"] > 0
    assert report["resumed_rows"] > 0
    if not report["pad_exercised"]:
        # equal drains on both attempts = the box was too slow to build the
        # buffering asymmetry, not a data-plane failure (selfcheck notes)
        pytest.skip(f"pad path not exercised on this box: {report['notes']}")
    # the interesting regime actually occurred: unequal drains forced pads
    assert sum(report["pad_counts"]) > 0
    assert len(set(report["drained_real_per_process"])) > 1


def test_multiprocess_shuffled_stacked(tmp_path):
    """SEEDED shuffled sharded reading + stack_batches=2 delivery + stacked
    drain at 4 REAL processes (with the scan-mode
    drain): all hosts realize the identical permutation, the masked multiset
    covers the dataset exactly, the order matches the locally recomputed
    seeded plan, and the pod shuffle-quality rank-correlation bound holds on
    rows collected from real processes."""
    from petastorm_tpu.parallel.selfcheck import run_shuffled_check

    report = run_shuffled_check(num_processes=4, devices_per_process=2,
                                workdir=str(tmp_path), timeout=360.0)
    _skip_if_unrunnable(report, "shuffled check")
    assert report["ok"], report["failures"]
    assert report["units"] >= 8
    assert report["rho_global"] < 0.5


def test_multiprocess_mixed_decode(tmp_path):
    """'device-mixed' jpeg decode across a mesh spanning REAL processes:
    host-local bucket decode + global-array scatter;
    pixels all-gather bit-identical on every host and match the launcher's
    host decode within the hybrid tolerance."""
    from petastorm_tpu.native import image as native_image

    if not native_image.available():
        pytest.skip("native image library unavailable")
    from petastorm_tpu.parallel.selfcheck import run_mixed_check

    report = run_mixed_check(num_processes=2, devices_per_process=4,
                             workdir=str(tmp_path), timeout=300.0)
    _skip_if_unrunnable(report, "mixed check")
    assert report["ok"], report["failures"]
    assert report["max_pixel_err"] <= 6
    assert all(g.get("image", 0) <= 2 for g in report["geometries_per_host"])


def test_multiprocess_context_parallel(tmp_path):
    """Ring attention's ppermute K/V rotation and Ulysses' all_to_all cross
    REAL process boundaries: sequence-sharded loader delivery over a mesh
    spanning 2 OS processes, outputs matching a float64 full-attention
    reference on every host."""
    from petastorm_tpu.parallel.selfcheck import run_context_parallel_check

    report = run_context_parallel_check(num_processes=2,
                                        devices_per_process=2,
                                        workdir=str(tmp_path), timeout=240.0)
    _skip_if_unrunnable(report, "context-parallel check")
    assert report["ok"], report["failures"]
    assert report["err_ring"] < 2e-4
    assert report["err_uly"] < 2e-4


def test_multiprocess_distributed_write(tmp_path):
    """distributed_write_dataset through its DEFAULT coordination (real
    jax.distributed sync_global_devices barriers over Gloo, process identity
    from the runtime) - the path threading.Barrier simulations cannot reach -
    plus merged geometry stamping and exact all-host readback."""
    from petastorm_tpu.parallel.selfcheck import run_distributed_write_check

    report = run_distributed_write_check(num_processes=2,
                                         workdir=str(tmp_path), timeout=240.0)
    _skip_if_unrunnable(report, "distributed-write check")
    assert report["ok"], report["failures"]
    assert report["rows_read"] == 64
    assert all(n > 0 for n in report["files_per_host"])


def test_multiprocess_2d_mesh_dp_x_tp(tmp_path):
    """The standard pod layout across REAL processes: 2-D mesh, data axis
    crossing the process boundary, tensor parallelism inside each process;
    sequence-sharded delivery plus one jitted reduction over both axes must
    match a numpy reference and agree across hosts."""
    from petastorm_tpu.parallel.selfcheck import run_mesh2d_check

    report = run_mesh2d_check(num_processes=2, devices_per_process=2,
                              workdir=str(tmp_path), timeout=240.0)
    _skip_if_unrunnable(report, "2-D mesh check")
    assert report["ok"], report["failures"]
    assert report["mesh"] == {"data": 2, "model": 2}
