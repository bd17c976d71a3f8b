#!/usr/bin/env python
"""Compare two benchmark result files and gate CI on the delta.

Inputs: any two of

* a driver-captured ``BENCH_rNN.json`` (``{"tail": "<bench.py stdout>", ...}``)
* raw ``python bench.py`` stdout saved to a file (one JSON line per metric)
* a bare JSON object ``{"metric_name": value, ...}``

Metric lines recognized inside a tail/stdout::

    {"metric": "<name>", "value": <float>, ...}
    {"metric": "bench_summary", "metrics": {"<name>": [<value>, <vs_b>], ...}}

Usage::

    python tools/bench_compare.py BENCH_r03.json BENCH_r05.json
    python tools/bench_compare.py old.json new.json --fail-threshold 10
    python tools/bench_compare.py old.json new.json --json

``--fail-threshold PCT`` arms the gate: exit 1 when any shared metric
regresses by more than PCT percent (direction-aware - ``*_pct`` metrics
matching the lower-is-better markers fail on increase, everything else on
decrease).  Without it the comparison is report-only and always exits 0, so
the same command serves both a human diff and a CI gate on the bench
trajectory (host rates drift +-30% between sessions; pick thresholds
accordingly).

Warm-cache metrics (BENCH_r07+, docs/operations.md "Warm cache"): the
``warm_cache_*`` family gates like any rate, but note the two ratio-shaped
members are SAME-SESSION anchored and therefore drift-immune - treat a
regression in ``warm_cache_epoch2_vs_epoch1_ratio`` (warm epoch over cold
epoch; the ISSUE 7 target is vs_baseline >= 1.0 against its 3.0x bar) or in
``warm_cache_cross_reader_hit_rate`` (fraction of reader B's first-epoch
items served from the tier; 1.0 = fully warm) as a code regression even in
a session whose absolute rates drifted.

Service metrics (BENCH_r08+, docs/operations.md "Disaggregated ingest
service"): ``service_ingest_samples_per_sec`` is the remote fleet's
delivery rate (dispatcher + 2 worker subprocesses) and drifts with the
host like any absolute rate; ``service_inprocess_anchor_samples_per_sec``
is the same read through the in-process thread pool in the same session;
their quotient ``service_vs_inprocess_ratio`` is the SAME-SESSION-anchored,
drift-immune member - it prices the wire-transport tax, so a drop in the
RATIO means the service plane itself regressed even when both absolute
rates moved with the host.  History: r08 captured 0.36x on pickled frames;
the ISSUE 12 binary wire plane carries an ABSOLUTE floor of 0.7x for the
remote client, and the ``service_colocated_vs_inprocess_ratio`` member
(shm-armed co-located fleet, emitted only where the arena plane is live -
python >= 3.12) carries 0.9x.

Determinism metrics (BENCH_r09+, docs/operations.md "Reproducibility"):
``determinism_vs_off_ratio`` prices the ``deterministic='seed'`` reorder
stage against completion-order delivery, same-session anchored.  It also
carries an ABSOLUTE floor (see ``ABSOLUTE_FLOORS``): any candidate below
0.85x fails an armed gate even if the baseline file was already below it -
the ISSUE 10 acceptance bar is absolute, not relative.

Sequence metrics (BENCH_r10+, docs/operations.md "Token pipelines"):
``sequence_packed_vs_padded_ratio`` prices packed ``(batch, seq_len)``
delivery against the naive pad-to-max baseline under a fixed simulated
step per block - SAME-SESSION anchored (drift-immune), absolute floor
1.5x.  ``sequence_packing_fill_rate`` (real tokens / emitted slots) is a
pure property of the packer + corpus shape and carries the 0.85 absolute
floor from the ISSUE 11 acceptance bar; the two absolute-rate members
(``sequence_packed_tokens_per_sec`` / ``..._padded_anchor_...``) drift
with the host like any rate.

Transform-cache / planner metrics (BENCH_r13+, docs/operations.md
"Transform caching & the pipeline planner"): ``transform_warm_vs_cold_ratio``
prices a warm epoch of a transform-DOMINATED pipeline with post-transform
output caching armed against its own cold epoch (same session, fresh tier
per round - drift-immune); its ``vs_baseline`` compares against the 13.5x
decode-only warm ratio of BENCH_r07, and the 3.0 absolute floor catches
output caching silently disarming.  ``transform_warm_vs_decode_only_warm_
ratio`` (floor 1.2) isolates what caching the transform's OUTPUT adds over
caching only the decode.  ``planner_cold_start_ratio`` (floor 1.2) is
explore-from-bad-knobs time-to-90%-of-steady over flight-profile-seeded
time-to-90% - the planner's cold-start win; ``planner_time_to_90pct_seconds``
is the seeded arm's absolute t90 (lower is better via the ``time_to``
marker).

Tracing metrics (ISSUE 19, docs/operations.md "Distributed tracing &
fleet view"): ``service_trace_armed_vs_untraced_ratio`` prices arming
per-item distributed tracing (``trace_items=8`` - 1-in-8 wire items carry
a trace context and collect per-hop monotonic stamps at dispatcher and
workers, merged client-side into spans + ``service.hop.*`` histograms)
against the identical untraced fleet read, interleaved in the same
session (drift-immune).  Absolute floor 0.98 = the <= 2% overhead
acceptance bar: tracing is meant to be cheap enough to leave sampled-on
in production, so a candidate below the floor fails an armed gate even
against a baseline that was already below it.  The two absolute-rate
members (``service_trace_armed_samples_per_sec`` /
``service_untraced_anchor_samples_per_sec``) drift with the host.

Autoscale metrics (BENCH_r12+, docs/operations.md "Fleet autoscaling &
QoS"): ``autoscale_vs_static_ratio`` prices the closed loop - an
undersized 1-worker fleet plus a live ``AutoscaleSupervisor`` over a
fleet statically sized right from the start, same session (drift-immune),
INCLUDING the loop's detect->spawn->register reaction window.  Absolute
floor 0.8x (the ISSUE 14 acceptance bar); the two absolute-rate members
(``autoscale_fleet_samples_per_sec`` /
``autoscale_static_anchor_samples_per_sec``) drift with the host.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

#: substrings marking a metric where SMALLER is better (idle/stall
#: percentages, latency ratios, time-to-threshold seconds); everything else
#: is treated as a rate
LOWER_IS_BETTER_MARKERS = ("idle_pct", "stall_pct", "latency",
                           "latent_vs_local", "time_to")

#: metric -> minimum acceptable value: an armed gate fails a candidate
#: BELOW the floor regardless of the baseline (absolute acceptance bars,
#: immune to a baseline that was itself captured in a bad session)
ABSOLUTE_FLOORS = {
    # ISSUE 10: deterministic-mode throughput >= 0.85x completion-order
    "determinism_vs_off_ratio": 0.85,
    # ISSUE 11: packed delivery >= 1.5x the pad-to-max baseline, and the
    # packer must fill >= 85% of emitted (batch, seq_len) slots
    "sequence_packed_vs_padded_ratio": 1.5,
    "sequence_packing_fill_rate": 0.85,
    # ISSUE 12: the binary wire plane must hold a remote service client at
    # >= 0.7x in-process (vs 0.35x on the old pickled frames), and an
    # shm-armed co-located client at >= 0.9x (metric emitted only on
    # runtimes where the arena plane is live, python >= 3.12)
    "service_vs_inprocess_ratio": 0.7,
    "service_colocated_vs_inprocess_ratio": 0.9,
    # ISSUE 14: a 1-worker fleet + the live autoscale supervisor must land
    # within 0.8x of a statically right-sized fleet on the same read -
    # the closed loop's detect->spawn->register latency is what's priced
    "autoscale_vs_static_ratio": 0.8,
    # ISSUE 15: a transform-dominated warm epoch with post-transform caching
    # must run >= 3x its cold epoch (the headline target is beating the
    # decode-only 13.5x - gated via vs_baseline in the note - but the
    # absolute floor catches output caching silently disarming), and output
    # caching must beat decode-only caching on the same warm epoch by 1.2x
    "transform_warm_vs_cold_ratio": 3.0,
    "transform_warm_vs_decode_only_warm_ratio": 1.2,
    # ISSUE 15: a flight-profile-seeded cold start must reach 90% of
    # steady-state delivery at least 1.2x sooner than the runtime loop
    # climbing from bad static knobs
    "planner_cold_start_ratio": 1.2,
    # ISSUE 19: arming per-item distributed tracing (trace_items=8) must
    # cost <= 2% of untraced fleet throughput in the same session
    "service_trace_armed_vs_untraced_ratio": 0.98,
}


def lower_is_better(name: str) -> bool:
    """True when a decrease in ``name`` is an improvement."""
    return any(m in name for m in LOWER_IS_BETTER_MARKERS)


def load_metrics(path: str) -> Dict[str, float]:
    """Extract ``{metric: value}`` from a bench artifact (see module doc)."""
    with open(path) as f:
        text = f.read()
    lines = text.splitlines()
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict):
        if "tail" in obj:            # driver-captured BENCH_rNN.json
            lines = str(obj["tail"]).splitlines()
        elif "metric" not in obj:    # bare {name: value} map
            return {str(k): float(v if not isinstance(v, (list, tuple))
                                  else v[0])
                    for k, v in obj.items()
                    if isinstance(v, (int, float, list, tuple))}
    metrics: Dict[str, float] = {}
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            entry = json.loads(line)
        except ValueError:
            continue
        if not isinstance(entry, dict):
            continue
        if entry.get("metric") == "bench_summary":
            for name, value in entry.get("metrics", {}).items():
                if isinstance(value, (list, tuple)):
                    value = value[0]
                metrics[str(name)] = float(value)
        elif "metric" in entry and isinstance(entry.get("value"),
                                              (int, float)):
            metrics[str(entry["metric"])] = float(entry["value"])
    if not metrics:
        raise SystemExit(f"{path}: no bench metrics found (expected bench.py"
                         " JSON lines, a BENCH_rNN.json capture, or a bare"
                         " metric map)")
    return metrics


def compare(old: Dict[str, float], new: Dict[str, float]) -> List[Dict]:
    """Per-metric rows: value pair, signed delta pct, and the direction-aware
    ``regression_pct`` (how much WORSE the new value is; <= 0 = no worse).

    A baseline metric MISSING from the candidate is the worst possible
    regression (the bench stopped measuring it - e.g. it crashed mid-run),
    so it carries ``regression_pct = inf`` and trips any armed gate; a NEW
    metric absent from the baseline is not a regression.  A zero baseline
    admits no percentage, but a direction-worse move off zero still gates
    (``inf``)."""
    rows = []
    for name in sorted(set(old) | set(new)):
        a, b = old.get(name), new.get(name)
        row: Dict = {"metric": name, "old": a, "new": b,
                     "lower_is_better": lower_is_better(name)}
        if a is not None and b is None:
            row["regression_pct"] = float("inf")
        elif a is not None and b is not None and a != 0:
            delta_pct = (b - a) / abs(a) * 100.0
            row["delta_pct"] = delta_pct
            row["regression_pct"] = (delta_pct if row["lower_is_better"]
                                     else -delta_pct)
        elif a == 0 and b is not None and b != a:
            row["regression_pct"] = (float("inf")
                                     if (b > a) == row["lower_is_better"]
                                     else 0.0)
        rows.append(row)
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="bench_compare",
        description="Print per-metric deltas between two bench result files;"
                    " optionally fail on regression (CI gate)")
    parser.add_argument("old", help="baseline bench file")
    parser.add_argument("new", help="candidate bench file")
    parser.add_argument("--fail-threshold", type=float, default=None,
                        metavar="PCT",
                        help="exit 1 when any shared metric regresses by more"
                             " than PCT percent (unset = report-only)")
    parser.add_argument("--metrics", nargs="+", default=None,
                        help="only compare these metric names")
    parser.add_argument("--json", action="store_true",
                        help="print one JSON object instead of a table")
    args = parser.parse_args(argv)

    old = load_metrics(args.old)
    new = load_metrics(args.new)
    if args.metrics:
        old = {k: v for k, v in old.items() if k in args.metrics}
        new = {k: v for k, v in new.items() if k in args.metrics}
    rows = compare(old, new)
    for r in rows:
        floor = ABSOLUTE_FLOORS.get(r["metric"])
        if floor is not None and r["new"] is not None and r["new"] < floor:
            r["below_floor"] = floor
    failures = [r for r in rows
                if args.fail_threshold is not None
                and (r.get("regression_pct", 0.0) > args.fail_threshold
                     or "below_floor" in r)]

    if args.json:
        print(json.dumps({"rows": rows,
                          "fail_threshold": args.fail_threshold,
                          "failures": [r["metric"] for r in failures]}))
    else:
        width = max([len(r["metric"]) for r in rows] + [6])
        print(f"{'metric':<{width}} {'old':>14} {'new':>14} {'delta%':>8}")
        for r in rows:
            old_s = f"{r['old']:.2f}" if r["old"] is not None else "-"
            new_s = f"{r['new']:.2f}" if r["new"] is not None else "-"
            delta = r.get("delta_pct")
            delta_s = f"{delta:+7.1f}%" if delta is not None else "       -"
            note = " (lower is better)" if r["lower_is_better"] else ""
            if "below_floor" in r:
                note += f" [below absolute floor {r['below_floor']:g}]"
            flag = "  << REGRESSION" if r in failures else ""
            print(f"{r['metric']:<{width}} {old_s:>14} {new_s:>14}"
                  f" {delta_s}{note}{flag}")
        if args.fail_threshold is not None:
            print(f"gate: {len(failures)} metric(s) regressed more than"
                  f" {args.fail_threshold:g}%"
                  + (f": {', '.join(r['metric'] for r in failures)}"
                     if failures else ""))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
