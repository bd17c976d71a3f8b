"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration's JSON file is the one ``configs`` gives; the traffic mix is
``benchmarks/traffic/<traffic>.json``; each metric is read by
``benchmarks/metrics/<metric name>.py``; the configuration names its
pipeline (``benchmarks.pipelines.<name>``) and its consumer
(``benchmarks.consumers.<name>``).  Adding a cell, mix, configuration or
metric is adding files and entries: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the file at ``path`` (metric names hold dots, so the file is
    loaded by path, not by package import)."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"no file {path} for {name!r}")
    mod_name = "benchmarks._loaded." + name.replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs: dataclasses look their module up by name
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    moves: Optional[str] = None
    workloads: Optional[List[str]] = None

    def reader(self, bench_dir: str = BENCH_DIR):
        """``benchmarks/metrics/<name>.py``: ``read(record)`` gives the
        value, or None where the record holds nothing for it."""
        return load_module(os.path.join(bench_dir, "metrics",
                                        self.name + ".py"), self.name)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)
    bench_dir: str = BENCH_DIR

    def pipeline(self):
        # a package import, so that spawned processes can unpickle what
        # the pipeline sends them
        return importlib.import_module(
            "benchmarks.pipelines." + self.config["pipeline"])

    def consumer(self):
        return importlib.import_module(
            "benchmarks.consumers." + self.config["consumer"])


def _metrics(entries) -> List[Metric]:
    return [Metric(name=m["name"], unit=m["unit"], moves=m.get("moves"),
                   workloads=m.get("workloads"))
            for m in entries]


def load_cell(workload: str, benchmark_json: Optional[str] = None,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell named ``workload``, with its files loaded and the metrics
    it reports: an end-to-end metric without ``workloads`` is reported
    everywhere; a per-layer metric without ``workloads`` wherever the
    end-to-end metric it moves is."""
    root = os.path.dirname(bench_dir)
    spec = load_json(benchmark_json or os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known:"
                       f" {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in _metrics(spec["end_to_end"])
           if m.workloads is None or workload in m.workloads]
    e2e_names = {m.name for m in e2e}
    per_layer = [m for m in _metrics(spec["per_layer"])
                 if (workload in m.workloads if m.workloads is not None
                     else m.moves in e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic=traffic,
                end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


def merged(base: dict, over: Optional[dict]) -> dict:
    """``base`` with ``over``'s keys replaced, recursively (the rehearsal
    sizes of a configuration or mix)."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = (merged(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out

