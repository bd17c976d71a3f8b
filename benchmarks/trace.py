"""Reduce a profiler trace of the timed window to what the per-layer
metrics read: device busy time, device time per program and per
operation, and the device's idle gaps labelled by what the benchmark's
host loop was doing in them.

The trace is JAX's ``.xplane.pb``.  Device planes are ``/device:TPU:<n>``;
their ``XLA Modules`` line holds one event per program run
(``jit_train_step(<hash>)``) and their ``XLA Ops`` line one per operation
(the event name is the HLO instruction, ``%fusion.3 = ...``).  The
benchmark's spans (``input_wait``, ``dispatch``, ``block``) are
``TraceAnnotation`` events on the host plane, on the same clock.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from typing import Dict, List, Tuple

BENCH_SPANS = ("input_wait", "dispatch", "block")
#: what an idle gap is labelled with, by the host span it falls in
GAP_LABELS = {"input_wait": "input_wait", "dispatch": "dispatch"}
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE = re.compile(r"^(.*)\(\d+\)$")
_OPCODE = re.compile(r"= .*? ([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_RESULT = re.compile(r"^%[\w.\-]+ = (\w+\[[\d,]*\])")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``: an unknown kind is
    an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["chips"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(table['chips'])}")
    return table["chips"][device_kind]


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def op_form(event_name: str) -> Tuple[str, List[str]]:
    """(opcode, operand names) of an HLO instruction's text."""
    m = _OPCODE.search(event_name)
    if not m:
        return "", []
    return m.group(1), _OPERAND.findall(event_name[m.end():])


def op_result_and_target(event_name: str) -> Tuple[str, str]:
    """(result type without layout, custom-call target or "") of an HLO
    instruction's text: ``bf16[256,150528]``, ``tpu_custom_call``."""
    r, t = _RESULT.search(event_name), _TARGET.search(event_name)
    return (r.group(1) if r else ""), (t.group(1) if t else "")


def module_name(event_name: str) -> str:
    """``jit_train_step(8940132579084054816)`` -> ``jit_train_step``."""
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _label(gap: Tuple[float, float], spans, starts) -> str:
    """The benchmark span that covers most of ``gap`` (``spans`` sorted by
    start, ``starts`` their starts; the loop's spans do not overlap)."""
    best, label = 0.0, "other"
    k = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
    while k < len(spans) and spans[k][1] < gap[1]:
        name, a, b = spans[k]
        overlap = min(b, gap[1]) - max(a, gap[0])
        if name in GAP_LABELS and overlap > best:
            best, label = overlap, GAP_LABELS[name]
        k += 1
    return label


def reduce_events(host_spans, devices) -> dict:
    """``host_spans``: [(name, start_ns, end_ns)] of the benchmark's spans;
    ``devices``: {index: {"modules": [(name, start_ns, dur_ns)],
    "ops": [(name, start_ns, dur_ns)]}}.  Times in seconds out."""
    # the measured window, as the end-to-end metrics take it: from the
    # first step completed (the end of the first ``block``) to the last
    blocks = sorted(b for n, _, b in host_spans if n == "block")
    if len(blocks) >= 2:
        lo, hi = blocks[0], blocks[-1]
    else:
        lo = min(a for _, a, _ in host_spans)
        hi = max(b for _, _, b in host_spans)
    spans = sorted(((n, a, b) for n, a, b in host_spans),
                   key=lambda sp: sp[1])
    starts = [a for _, a, _ in spans]
    n_dev = max(len(devices), 1)
    busy = 0.0
    modules: Dict[str, Dict[str, float]] = {}
    ops: Dict[str, Dict[str, float]] = {}
    gaps: List[Tuple[str, float]] = []
    for dev in devices.values():
        mods = sorted((s, s + d, module_name(n)) for n, s, d in dev["modules"]
                      if lo <= s < hi)
        for s, e, name in mods:
            m = modules.setdefault(name, {"seconds": 0.0, "count": 0})
            m["seconds"] += (e - s) / 1e9 / n_dev
            m["count"] += 1 / n_dev
        intervals = []
        k = 0
        for name, s, d in sorted(dev["ops"], key=lambda o: o[1]):
            iv = _clip(s, s + d, lo, hi)
            if iv is None:
                continue
            intervals.append(iv)
            while k < len(mods) and mods[k][1] < s:
                k += 1
            owner = (mods[k][2] if k < len(mods) and mods[k][0] <= s
                     else "?")
            key = f"{owner}/{op_name(name)}"
            o = ops.get(key)
            if o is None:
                opcode, operands = op_form(name)
                result, target = op_result_and_target(name)
                o = ops[key] = {"seconds": 0.0, "count": 0,
                                "custom_call": opcode == "custom-call",
                                "opcode": opcode, "operands": operands,
                                "result": result, "target": target}
            o["seconds"] += (iv[1] - iv[0]) / 1e9 / n_dev
            o["count"] += 1 / n_dev
        merged = union(intervals)
        busy += sum(b - a for a, b in merged) / 1e9 / n_dev
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label((a, b), spans, starts),
                             (b - a) / 1e9 / n_dev))
    idle_by_label: Dict[str, float] = {}
    for label, s in gaps:
        idle_by_label[label] = idle_by_label.get(label, 0.0) + s
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy, "devices": n_dev,
            "modules": modules, "ops": ops, "gaps": gaps[:50],
            "idle_by_label": idle_by_label}


def load_events(path: str):
    """Host spans and per-device modules and operations from an xplane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
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
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in BENCH_SPANS)
    return host, devices


def reduce_trace(path: str) -> dict:
    host, devices = load_events(path)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if not host:
        raise ValueError(f"{path}: none of the benchmark's spans"
                         f" {BENCH_SPANS} in the trace")
    return reduce_events(host, devices)


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by what the host loop was doing, each with its seconds."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1]["seconds"])
    return {"device_ops": [[k, v["seconds"]] for k, v in ops[:top]],
            "idle_gaps": [[label, s] for label, s in reduced["gaps"][:top]]}
