"""The program's own spans in the window's trace, and the device idle they
overlap.

While a profiler session runs, ``petastorm_tpu.telemetry`` opens an
annotation named ``petastorm/<span>`` for each stage and span, on the
thread that does the work: one line of the xplane's host plane per thread,
on the clock of the device planes.  This module reduces them over the
window ``trace.py`` takes (from the end of the first ``block`` to the end
of the last) to:

- ``program_spans``: ``{name: {"seconds", "count"}}``, the time in each
  span clipped to the window, summed over threads;
- ``idle_by_program_span``: ``{name: seconds}``, the device-idle time, mean
  over chips, that the span overlaps on any thread;
- ``idle_staging_s``: the device-idle time, mean over chips, in which a
  thread is in ``device-transfer`` but not in its ``commit`` child: the
  idle that host-side staging overlaps.  None without ``device-transfer``
  spans;
- ``gaps``: the longest idle gaps of any chip, each with its chip and the
  seconds of it that each span overlaps (``trace.breakdown`` gives a
  gap's seconds over the chip count).

A trace of a program that opens no such annotation reduces to empty maps.

    python3 -m benchmarks.program_spans <trace.xplane.pb>

prints the reduction, with the benchmark's own idle labels beside it.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

from benchmarks import trace

PREFIX = "petastorm/"
STAGING = "device-transfer"
COMMIT = "commit"

Intervals = List[Tuple[float, float]]


def load_events(path: str):
    """``trace.load_events``'s host spans and devices, plus the program's
    spans per host thread line: ``{(plane, line): [(name, start_ns,
    end_ns)]}``, in one read of the xplane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices, program = [], {}, {}
    for plane in pd.planes:
        m = trace._DEVICE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key].extend((e.name, e.start_ns, e.duration_ns)
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name in trace.BENCH_SPANS:
                        host.append((e.name, e.start_ns, end))
                    elif e.name.startswith(PREFIX):
                        program.setdefault((plane.name, i), []).append(
                            (e.name[len(PREFIX):], e.start_ns, end))
    return host, devices, program


def window(host_spans) -> Tuple[float, float]:
    """The measured window, as ``trace.reduce_events`` takes it."""
    blocks = sorted(b for n, _, b in host_spans if n == "block")
    if len(blocks) >= 2:
        return blocks[0], blocks[-1]
    return (min(a for _, a, _ in host_spans),
            max(b for _, _, b in host_spans))


def idle_intervals(dev: dict, lo: float, hi: float) -> Intervals:
    """Where no operation of one device ran, inside the window."""
    busy = trace.union([iv for iv in (trace._clip(s, s + d, lo, hi)
                                      for _, s, d in dev["ops"]) if iv])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def overlap(a: Intervals, b: Intervals) -> float:
    """Total overlap of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def minus(a: Intervals, b: Intervals) -> Intervals:
    """``a`` without ``b``; both sorted and disjoint."""
    out: Intervals = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, start = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > start:
                out.append((start, b[k][0]))
            start = max(start, b[k][1])
            k += 1
        if hi > start:
            out.append((start, hi))
    return out


def reduce_events(host_spans, devices, program, top: int = 10) -> dict:
    """``host_spans`` and ``devices`` as ``trace.reduce_events`` takes them;
    ``program``: ``{line: [(name, start_ns, end_ns)]}``.  Seconds out."""
    lo, hi = window(host_spans)
    idles = [idle_intervals(d, lo, hi) for d in devices.values()]
    n_dev = max(len(idles), 1)

    def idle_s(merged: Intervals) -> float:
        return sum(overlap(merged, idle) for idle in idles) / 1e9 / n_dev

    spans: Dict[str, Dict[str, float]] = {}
    by_name: Dict[str, Intervals] = {}
    staging: Intervals = []
    seen_staging = False
    for events in program.values():
        clipped: Dict[str, Intervals] = {}
        for name, a, b in events:
            iv = trace._clip(a, b, lo, hi)
            if iv is None:
                continue
            s = spans.setdefault(name, {"seconds": 0.0, "count": 0})
            s["seconds"] += (iv[1] - iv[0]) / 1e9
            s["count"] += 1
            clipped.setdefault(name, []).append(iv)
        for name, ivs in clipped.items():
            by_name.setdefault(name, []).extend(ivs)
        if STAGING in clipped:
            seen_staging = True
            staging += minus(trace.union(clipped[STAGING]),
                             trace.union(clipped.get(COMMIT, [])))
    merged = {name: trace.union(ivs) for name, ivs in by_name.items()}
    longest = sorted(((chip, a, b) for chip, idle in zip(devices, idles)
                      for a, b in idle), key=lambda g: g[1] - g[2])[:top]
    gaps = []
    for chip, a, b in longest:
        over = {name: overlap([(a, b)], ivs) / 1e9
                for name, ivs in merged.items()}
        gaps.append({"chip": chip, "seconds": (b - a) / 1e9,
                     "spans": {n: s for n, s in over.items() if s > 0}})
    return {"window_s": (hi - lo) / 1e9,
            "program_spans": spans,
            "idle_by_program_span": {name: idle_s(ivs)
                                     for name, ivs in merged.items()},
            "idle_staging_s": (idle_s(trace.union(staging))
                               if seen_staging else None),
            "gaps": gaps}


def reduce_trace(path: str) -> dict:
    return reduce_events(*load_events(path))


_REDUCED: Dict[str, dict] = {}


def for_record(rec: dict, bench_dir: str) -> Optional[dict]:
    """The reduction of the trace of the traced run ``rec`` records: the
    newest trace under its configuration's cells' run directories
    (``<bench_dir>/.runs/<cell>/trace``), if its window is the record's.
    None for an untraced run or a trace of another run."""
    reduced = rec.get("trace")
    if not reduced:
        return None
    name = rec.get("config", {}).get("name", "")
    paths = glob.glob(os.path.join(bench_dir, ".runs", f"{name}.*", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    if path not in _REDUCED:
        _REDUCED[path] = reduce_trace(path)
    out = _REDUCED[path]
    if abs(out["window_s"] - reduced["window_s"]) > 1e-9:
        return None
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m benchmarks.program_spans"
              " <trace.xplane.pb>", file=sys.stderr)
        return 2
    out = reduce_trace(argv[0])
    out["idle_by_label"] = trace.reduce_trace(argv[0])["idle_by_label"]
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
