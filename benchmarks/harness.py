"""One run of one cell: set-up, warm-up, the timed window, the check and
the metrics.  ``run.py`` adds the look for a chip and prints the result;
tests call :func:`run_cell` directly at rehearsal sizes.

The loop keeps one step in flight: after it dispatches step *i* it blocks
on step *i-1*, and step *i-1* counts as done then.  The window opens at the
first completion after warm-up and closes at the first completion
``seconds`` later; every rate is the work of the steps completed after the
opening over the time between the two.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import spec as spec_mod

#: at most this much of a traced run's window is traced: the trace of a
#: longer one is slow to read back and adds nothing a per-layer mean needs
TRACE_SECONDS = 10.0
#: batches kept from the window for the check, drawn from the seed
SAMPLES = 4


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed_words(seed: int) -> List[int]:
    """The run's seed (any whole number) as 32-bit words."""
    s = int(seed) % 2 ** 64
    return [s & 0xFFFFFFFF, s >> 32]


def seed_rng(seed: int, salt: int) -> np.random.Generator:
    """A generator from the run's seed and a salt."""
    return np.random.default_rng([salt] + seed_words(seed))


def key_seed(seed: int) -> int:
    """A seed for ``jax.random.PRNGKey`` (31 bits) from the run's seed."""
    return int(seed_rng(seed, 5).integers(0, 2 ** 31))


class Window:
    """What the timed loop saw: completion times, input waits, and the
    steps it sampled for the check."""

    def __init__(self):
        self.completions: List[float] = []   # host clock, one per step done
        self.waits: List[float] = []         # next(loader) per window step
        self.samples: Dict[int, object] = {}  # window step -> kept arrays
        self.first_step = 0                   # pipeline step index of step 0

    @property
    def seconds(self) -> float:
        return self.completions[-1] - self.completions[0]

    @property
    def steps_done(self) -> int:
        """Steps completed after the window opened."""
        return len(self.completions) - 1

    def gaps_ms(self) -> List[float]:
        c = self.completions
        return [(b - a) * 1e3 for a, b in zip(c, c[1:])]


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def warm_up(pipe, steps: int) -> int:
    """``steps`` steps with one in flight, then drained: every program the
    window runs is compiled and the feed's queues are full."""
    prev = None
    for i in range(steps):
        handle, _ = pipe.step(pipe.next_batch(), i)
        if prev is not None:
            prev.block_until_ready()
        prev = handle
    if prev is not None:
        prev.block_until_ready()
    return steps


def timed_window(pipe, first_step: int, seconds: float,
                 rng: np.random.Generator, keep: int = SAMPLES) -> Window:
    w = Window()
    w.first_step = first_step
    prev = None
    i = first_step
    seen = 0
    while True:
        a = time.perf_counter()
        with _annotate("input_wait"):
            batch = pipe.next_batch()
        w.waits.append(time.perf_counter() - a)
        with _annotate("dispatch"):
            handle, kept = pipe.step(batch, i)
        # reservoir sample of the window's steps, drawn from the seed
        if seen < keep:
            w.samples[i] = kept
        else:
            j = int(rng.integers(0, seen + 1))
            if j < keep:
                del w.samples[sorted(w.samples)[j]]
                w.samples[i] = kept
        seen += 1
        if prev is not None:
            with _annotate("block"):
                prev.block_until_ready()
            w.completions.append(time.perf_counter())
            if w.completions[-1] - w.completions[0] >= seconds:
                break
        prev = handle
        i += 1
    handle.block_until_ready()
    return w


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak device memory of the fullest chip: the buffers in use at their
    peak plus what the runtime reserved for the programs' temporaries at
    its peak (on a TPU the step's activations live in the latter)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run_cell(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_start: float, devices,
             data_root: Optional[str] = None,
             trace_dir: Optional[str] = None) -> dict:
    """Run the cell once on ``devices``; returns the result (printed last
    by run.py).  ``rehearse`` takes the configuration's and the mix's
    rehearsal sizes."""
    import jax
    from jax.sharding import Mesh

    config = cell.config
    traffic = cell.traffic
    if rehearse:
        config = spec_mod.merged(config, config.get("rehearse"))
        traffic = spec_mod.merged(traffic, traffic.get("rehearse"))
    data_root = data_root or os.path.join(cell.bench_dir, ".data")
    trace_dir = trace_dir or os.path.join(cell.bench_dir, ".runs", cell.name,
                                          "trace")
    devices = list(devices)[:cell.chips]
    mesh = Mesh(np.asarray(devices), ("data",))
    pipe = cell.pipeline().Pipeline(
        config=config, traffic=traffic, consumer=cell.consumer(),
        seed=seed, mesh=mesh, telemetry=trace,
        data_dir=os.path.join(data_root, f"{cell.config_name}-{seed}"))
    try:
        t0 = time.perf_counter()
        pipe.prepare()
        t1 = time.perf_counter()
        pipe.open()
        t2 = time.perf_counter()
        first = warm_up(pipe, int(traffic["warmup_steps"]))
        log(f"set-up: start {t0 - t_start:.2f} s, data {t1 - t0:.2f} s,"
            f" open {t2 - t1:.2f} s, warm-up {time.perf_counter() - t2:.2f} s")
        window_s = min(seconds, TRACE_SECONDS) if trace else seconds
        counters0 = pipe.counters()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            w = timed_window(pipe, first, window_s, seed_rng(seed, 1))
        finally:
            if trace:
                jax.profiler.stop_trace()
        counters1 = pipe.counters()
        setup_s = w.completions[0] - t_start
        peak = memory_peak_bytes(devices)
        # what the check needs comes to the host; the program's state and
        # feed are freed before the reference runs
        kept = pipe.fetch(w.samples)
        w.samples = None
    finally:
        pipe.close()
    first_done = w.first_step + 1
    work = sum(pipe.work(i) for i in range(first_done,
                                           first_done + w.steps_done))
    checks = pipe.check(kept)
    rec = {"window": {"seconds": w.seconds, "steps": w.steps_done,
                      "work": work, "gaps_ms": w.gaps_ms(),
                      "waits_s": w.waits},
           "setup_s": setup_s,
           "counters": {"start": counters0, "end": counters1},
           "chips": cell.chips, "config": config, "traffic": traffic,
           "shapes": pipe.shapes(), "programs": pipe.programs()}
    result_device = dict(device_info(devices), memory_peak_bytes=peak)
    breakdown = None
    if trace and devices[0].platform == "tpu":
        from benchmarks import trace as trace_mod

        path = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
        reduced = trace_mod.reduce_trace(path)
        rec["trace"] = reduced
        rec["peaks"] = trace_mod.peaks(devices[0].device_kind)
        result_device["busy_s"] = reduced["busy_s"]
        result_device["window_s"] = reduced["window_s"]
        breakdown = trace_mod.breakdown(reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader(cell.bench_dir).read(rec)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": w.steps_done,
           "failed": 0 if correct else w.steps_done,
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
