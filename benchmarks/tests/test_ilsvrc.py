"""The ILSVRC cell and the host-decode cell at rehearsal sizes: every phase
runs on the CPU and reads ``correct``, over images of about 24-72 px in
four canvases; each planted fault of the canvas path comes out not
correct (``benchmarks/ilsvrc_faults.py``), as does the one-bf16-pass IDCT
over enough pixels."""

import jax
import numpy as np
import pytest

from benchmarks import harness, ilsvrc_faults, spec

CELL = "ilsvrc_rn50.rrc"


def run(workload, tmp_path, variant="none", seed=3000000011, seconds=1.5):
    cell = spec.load_cell(workload)
    with ilsvrc_faults.variant(variant, cell):
        return harness.run_cell(cell, seed, seconds, False, True, 0.0,
                                jax.devices(),
                                data_root=str(tmp_path / "data"))


@pytest.mark.parametrize("workload", [CELL, "imagenet_rn50.hostdecode"])
def test_sound_rehearsal_is_correct(workload, tmp_path):
    out = run(workload, tmp_path)
    assert out["correct"], out["checks"]
    if workload == CELL:
        assert {"extent_mismatches", "crop_box_errors", "flip_errors",
                "crop_pixel_max_diff"} <= set(out["checks"])


@pytest.mark.parametrize("fault,fails", [
    ("crop_over_canvas", "crop_pixel_max_diff"),
    ("crop_reads_outside", "crop_pixel_max_diff"),
    ("edges_zero", "pixel_max_diff"),
    ("answer_altered", "label_mismatches"),
    ("nonfinite_loss", "nonfinite_losses")])
def test_fault_is_not_correct(fault, fails, tmp_path):
    out = run(CELL, tmp_path, fault)
    assert not out["correct"], out["checks"]
    c = out["checks"][fails]
    assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("name,correct", [("none", True),
                                          ("idct_default", False)])
def test_one_pass_idct_fails_over_enough_pixels(name, correct, tmp_path,
                                                monkeypatch):
    """The pixel tail separates a float32 IDCT from one bf16 pass only over
    enough pixels: 4-row batches of images about 110-200 px a side."""
    over = {"batch_per_chip": 4,
            "data": {"rows": 48, "row_group_rows": 12, "rows_per_file": 24,
                     "geometry": {"long_side": 200, "long_min": 110,
                                  "tail_share": 0.0, "tail_max": 208}},
            "model": {"input_side": 32}}
    real = spec.load_cell

    def load_cell(workload):
        cell = real(workload)
        cell.config["rehearse"] = over
        cell.traffic["rehearse"]["loader"] = {
            "pad_shapes": {"image": [[208, 208, 3]]}}
        return cell

    monkeypatch.setattr(spec, "load_cell", load_cell)
    out = run(CELL, tmp_path, name, seconds=1.0)
    assert out["correct"] == correct, out["checks"]
    if not correct:
        c = out["checks"]["pixels_3_off_per_million"]
        assert c["value"] > c["limit"], out["checks"]


def test_cells_report_their_metrics():
    """The new cells carry the image cells' metrics; only the ILSVRC cell
    reports the canvas path's three."""
    new = {"mixed_finish_roofline_pct", "canvas_fill_pct",
           "mixed_pack_ms_per_batch"}
    rrc = spec.load_cell(CELL)
    host = spec.load_cell("imagenet_rn50.hostdecode")
    hybrid = spec.load_cell("imagenet_rn50.hybrid")
    # the ILSVRC cell's step tail follows how its ~28 ms finishes fall
    # between steps, and spread too widely over seeds to be a bound
    assert {m.name for m in rrc.end_to_end} == {"images_per_s", "setup_s"}
    assert {m.name for m in host.end_to_end} == {"images_per_s",
                                                 "step_p95_ms", "setup_s"}
    rrc_layer = {m.name for m in rrc.per_layer}
    host_layer = {m.name for m in host.per_layer}
    assert new <= rrc_layer and not new & host_layer
    assert "jpeg_finish_roofline_pct" not in rrc_layer | host_layer
    assert rrc_layer - new == host_layer
    assert host_layer == {m.name for m in hybrid.per_layer} - {
        "jpeg_finish_roofline_pct"}


def _rec(counters0, counters1, **over):
    rec = {"counters": {"start": counters0, "end": counters1},
           "shapes": {"rows": 256, "chips": 1, "sampling": [[2, 2], [1, 1],
                                                            [1, 1]],
                      "mean_height": 386.0, "mean_width": 467.0},
           "programs": {"step": "jit_train_step",
                        "finish": "jit_finish_canvas"},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"modules": {
               "jit_train_step": {"seconds": 1.0, "count": 10},
               "jit_finish_canvas": {"seconds": 0.08, "count": 80}}}}
    rec.update(over)
    return rec


def test_canvas_metric_readers():
    rec = _rec({"loader.mixed_pixels_valid": 10.0,
                "loader.mixed_pixels_canvas": 20.0,
                "stage.mixed-pack.busy_s": 1.0,
                "loader.batches_delivered": 5.0},
               {"loader.mixed_pixels_valid": 40.0,
                "loader.mixed_pixels_canvas": 60.0,
                "stage.mixed-pack.busy_s": 1.5,
                "loader.batches_delivered": 15.0})
    read = {m.name: m.reader().read for m in spec.load_cell(CELL).per_layer}
    assert read["canvas_fill_pct"](rec) == pytest.approx(75.0)
    assert read["mixed_pack_ms_per_batch"](rec) == pytest.approx(50.0)
    from benchmarks import roofline

    ops, nbytes = roofline.jpeg_finish(256, 386, 467, [[2, 2], [1, 1],
                                                       [1, 1]])
    # 8 ms of finish a step: 80 calls over 10 steps
    want = 100.0 * max(ops / 197e12, nbytes / 819e9) / 0.008
    assert read["mixed_finish_roofline_pct"](rec) == pytest.approx(want)
    # a program without canvases leaves them silent
    bare = _rec({}, {}, programs={"step": "jit_train_step"})
    assert all(read[n](bare) is None for n in (
        "canvas_fill_pct", "mixed_pack_ms_per_batch",
        "mixed_finish_roofline_pct"))
    assert np.isfinite(want)
