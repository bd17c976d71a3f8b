"""A new configuration, traffic mix and metric are found by name: files
and BENCHMARK.json entries dropped into a copy of the benchmark, with no
edit to a file that is there, make a new cell that runs and reports."""

import hashlib
import json
import os
import shutil

import jax

from benchmarks import harness, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SKIP = shutil.ignore_patterns(".data", ".jax_cache", ".runs", "__pycache__")


def _digest(tree):
    h = hashlib.sha256()
    for d, _, files in sorted(os.walk(tree)):
        for f in sorted(files):
            if f.endswith((".py", ".json")):
                with open(os.path.join(d, f), "rb") as fh:
                    h.update(f.encode() + fh.read())
    return h.hexdigest()


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=SKIP)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = _digest(bench)

    # the new files
    cfg = json.loads((bench / "configs" / "imagenet_rn50.json").read_text())
    cfg["name"] = "dummy_cfg"
    cfg["batch_per_chip"] = 16
    (bench / "configs" / "dummy_cfg.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "hybrid.json").read_text())
    mix["why"] = "a dummy mix: host decode instead of hybrid"
    mix["reader"]["decode_placement"] = None
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "dummy_steps.py").write_text(
        "def read(rec):\n    return float(rec['window']['steps'])\n")
    # the new entries
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "dummy_cfg", "source": "https://example.org",
                         "file": "benchmarks/configs/dummy_cfg.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "dummy_cfg.dummy_mix", "config": "dummy_cfg",
                           "traffic": "dummy_mix", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "dummy_steps", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "images_per_s",
                           "workloads": ["dummy_cfg.dummy_mix"]})
    b["end_to_end"][0]["workloads"].append("dummy_cfg.dummy_mix")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    added = {"dummy_cfg.json", "dummy_mix.json", "dummy_steps.py"}

    cell = spec.load_cell("dummy_cfg.dummy_mix",
                          benchmark_json=str(tmp_path / "BENCHMARK.json"),
                          bench_dir=str(bench))
    assert cell.config["name"] == "dummy_cfg"
    assert cell.traffic["reader"]["decode_placement"] is None
    assert [m.name for m in cell.per_layer] == ["dummy_steps"]
    assert {m.name for m in cell.end_to_end} == {"images_per_s", "setup_s"}

    out = harness.run_cell(cell, 7, 1.0, True, True, 0.0, jax.devices(),
                           data_root=str(tmp_path / "data"),
                           trace_dir=str(tmp_path / "trace"))
    assert out["correct"], out["checks"]
    assert out["metrics"]["dummy_steps"]["value"] >= 1
    # nothing that was there changed
    for f in added:
        for sub in ("configs", "traffic", "metrics"):
            p = bench / sub / f
            if p.exists():
                p.unlink()
    assert _digest(bench) == before
