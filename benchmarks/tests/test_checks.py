"""The check has teeth: runs of the cells at rehearsal sizes, with the
look for a chip skipped and the timed path broken underneath, come out
not correct; so does each cell's control.  A sound run comes out correct.

The faults and controls are ``benchmarks/control.py``'s variants, the same
ones its chip runs read.
"""

import jax
import pytest

from benchmarks import control, harness, spec


def run(workload, tmp_path, variant="none", seed=11, seconds=1.5):
    cell = spec.load_cell(workload)
    with control.variant(variant, cell):
        return harness.run_cell(cell, seed, seconds, False, True, 0.0,
                                jax.devices(),
                                data_root=str(tmp_path / "data"))


CELLS = ["imagenet_rn50.hybrid", "tokens_gpt2.packed"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, tmp_path):
    out = run(workload, tmp_path)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged", "nonfinite_loss"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, tmp_path):
    out = run(workload, tmp_path, fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload,name", [
    ("imagenet_rn50.hybrid", "nearest_upsampling"),
    ("imagenet_rn50.hybrid", "normalize_bf16"),
    ("tokens_gpt2.packed", "truncate_long_docs")])
def test_control_is_not_correct(workload, name, tmp_path):
    out = run(workload, tmp_path, name, seed=13)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name,correct", [("none", True),
                                          ("idct_default", False)])
def test_one_pass_idct_fails_at_full_width(name, correct, tmp_path,
                                           monkeypatch):
    """The pixel tail separates a float32 IDCT from one bf16 pass only
    over enough pixels: 16 checked images at the cell's 224x224."""
    over = {"batch_per_chip": 4,
            "data": {"rows": 64, "row_group_rows": 16, "rows_per_file": 32,
                     "side": 224, "texture": {"shapes": [6, 14], "mid": 22,
                                              "fine": 22}}}
    real = spec.load_cell

    def load_cell(workload):
        cell = real(workload)
        cell.config["rehearse"] = over
        return cell

    monkeypatch.setattr(spec, "load_cell", load_cell)
    out = run("imagenet_rn50.hybrid", tmp_path, name, seed=17, seconds=1.0)
    assert out["correct"] == correct, out["checks"]


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch",
                                   "state_unchanged", "answer_altered"])
def test_four_chip_fault_is_not_correct(fault, tmp_path):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices (conftest asks the CPU for four)")
    out = run("imagenet_rn50.dp4", tmp_path, fault)
    assert not out["correct"], out["checks"]


def test_four_chip_sound_run_is_correct(tmp_path):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices (conftest asks the CPU for four)")
    out = run("imagenet_rn50.dp4", tmp_path)
    assert out["correct"], out["checks"]



def _delivery(tmp_path, ids, n_window):
    """``Pipeline._delivery_errors`` over a 16-row dataset of 4 row groups
    (ids 0-3, 4-7, ...) read by 2 workers."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from benchmarks.pipelines import images

    pq.write_table(pa.table({"id": pa.array(np.arange(16), pa.int64())}),
                   str(tmp_path / "part-0.parquet"), row_group_size=4)
    pipe = object.__new__(images.Pipeline)
    pipe.config = {"data": {"rows": 16}}
    pipe.traffic = {"reader": {"workers_count": 2}}
    pipe.data_dir = str(tmp_path)
    return pipe._delivery_errors(np.asarray(ids), n_window)


def _groups(order):
    return [i for g in order for i in range(4 * g, 4 * g + 4)]


@pytest.mark.parametrize("ids,n_window,errors", [
    # epochs in completion order: a copy of group 0 comes two epochs late,
    # after the window (12 groups), in the drain: sound
    (_groups([1, 0, 2, 3, 2, 1, 3, 1, 3, 2, 3, 0, 0, 0]), 48, 0),
    # group 2 never comes again after the first epoch
    (_groups([0, 1, 2, 3, 0, 1, 3, 0, 1, 3, 0, 1, 3]), 48, 4 * 2),
    # one group every step (a state left unchanged)
    (_groups([1] * 40), 160, 4 * 3 * 10),
    # the second half of group 1 replaced by its first half
    ([0, 1, 2, 3, 4, 5, 4, 5] + _groups([2, 3]), 16, 2),
])
def test_delivery_check_takes_completion_order(tmp_path, ids, n_window,
                                               errors):
    got = _delivery(tmp_path, ids, n_window)
    assert (got == 0) if errors == 0 else (got >= errors), got
