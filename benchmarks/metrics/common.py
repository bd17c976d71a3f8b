"""What several metric readers share.  A reader returns None where the run
record holds nothing for it, and the metric is then left out."""

from benchmarks import roofline


def delta(rec, name):
    """Window change of a program counter (telemetry, traced runs)."""
    c = rec.get("counters") or {}
    start, end = c.get("start") or {}, c.get("end") or {}
    if name not in end:
        return None
    return end[name] - start.get(name, 0.0)


def mean_input_wait_ms(rec):
    waits = rec["window"]["waits_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None


def module(rec, role):
    """(seconds per call on one chip, calls) of the program that plays
    ``role`` (the pipeline's ``programs()``), from the trace."""
    trace = rec.get("trace")
    name = rec["programs"].get(role)
    if not trace or name not in trace["modules"]:
        return None
    m = trace["modules"][name]
    return (m["seconds"] / m["count"], m["count"]) if m["count"] else None


def step_device_ms(rec):
    m = module(rec, "step")
    return None if m is None else 1e3 * m[0]


def device_idle_pct(rec):
    trace = rec.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def roofline_pct(rec, ops, nbytes, seconds):
    return roofline.share_pct(ops, nbytes, seconds, rec["peaks"])[0]
