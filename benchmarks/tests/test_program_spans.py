"""The program's spans in the trace (``program_spans.py``) and the four
metrics that read the program's stages, on hand-made events and records
and on the trace recorded on a TPU v5e, which predates the spans."""

import os

import pytest

from benchmarks import program_spans, spec, trace
from benchmarks.tests.test_trace import TESTDATA

MS = 1_000_000


def _metric(name):
    return spec.Metric(name=name, unit="-").reader()


def _events():
    """A 100 ms window; the device busy 20-50 and 70-90 ms.  The transfer
    thread stages 0-30 ms (committing 10-25) and 55-80 ms (no commit); a
    worker reads 40-60 ms inside its decode 35-65 ms."""
    host = [("block", 0, 0), ("input_wait", 5 * MS, 20 * MS),
            ("block", 90 * MS, 100 * MS)]
    dev = {0: {"modules": [("jit_step(1)", 20 * MS, 70 * MS)],
               "ops": [("%f.1 = f32[] fusion()", 20 * MS, 30 * MS),
                       ("%f.2 = f32[] fusion()", 70 * MS, 20 * MS)]}}
    program = {
        ("/host:CPU", 3): [("device-transfer", 0, 30 * MS),
                           ("commit", 10 * MS, 25 * MS),
                           ("device-transfer", 55 * MS, 80 * MS),
                           ("put", 56 * MS, 58 * MS)],
        ("/host:CPU", 5): [("decode", 35 * MS, 65 * MS),
                           ("read", 40 * MS, 60 * MS),
                           ("decode", 110 * MS, 120 * MS)],
    }
    return host, dev, program


def test_program_spans_and_the_idle_they_overlap():
    host, dev, program = _events()
    r = program_spans.reduce_events(host, dev, program)
    assert r["window_s"] == pytest.approx(0.1)
    spans = r["program_spans"]
    assert spans["device-transfer"] == {"seconds": pytest.approx(0.055),
                                        "count": 2}
    # the decode at 110 ms lies outside the window
    assert spans["decode"] == {"seconds": pytest.approx(0.03), "count": 1}
    assert spans["read"]["seconds"] == pytest.approx(0.02)
    idle = r["idle_by_program_span"]
    # idle: 0-20, 50-70, 90-100 ms
    assert idle["device-transfer"] == pytest.approx(0.020 + 0.015)
    assert idle["commit"] == pytest.approx(0.010)
    assert idle["decode"] == pytest.approx(0.015)
    assert idle["read"] == pytest.approx(0.010)
    assert idle["put"] == pytest.approx(0.002)
    # staging = transfer without its commit: 0-10, 25-30, 55-80 ms;
    # idle in it: 0-10 and 55-70 ms
    assert r["idle_staging_s"] == pytest.approx(0.025)
    # the longest gaps, each put down to the spans that overlap it
    assert [g["seconds"] for g in r["gaps"]] == pytest.approx(
        [0.02, 0.02, 0.01])
    assert {g["chip"] for g in r["gaps"]} == {0}
    assert r["gaps"][0]["spans"] == {"device-transfer": pytest.approx(0.02),
                                     "commit": pytest.approx(0.01)}
    assert set(r["gaps"][1]["spans"]) == {"device-transfer", "put",
                                          "decode", "read"}
    assert r["gaps"][2]["spans"] == {}


def test_spans_on_four_chips_average_their_idle():
    host, dev, program = _events()
    busy = {"modules": [], "ops": [("%f = f32[] fusion()", 0, 100 * MS)]}
    r = program_spans.reduce_events(host, {0: dev[0], 1: busy, 2: busy,
                                           3: busy}, program)
    assert r["idle_staging_s"] == pytest.approx(0.025 / 4)
    assert r["idle_by_program_span"]["read"] == pytest.approx(0.010 / 4)


def test_interval_helpers():
    a = [(0, 10), (20, 30)]
    b = [(5, 8), (9, 22), (25, 26)]
    assert program_spans.overlap(a, b) == 3 + 1 + 2 + 1
    assert program_spans.minus(a, b) == [(0, 5), (8, 9), (22, 25), (26, 30)]
    assert program_spans.minus(a, []) == a
    assert program_spans.minus([(0, 10)], [(0, 10)]) == []


def test_recorded_trace_keeps_its_reduction_and_has_no_program_spans():
    """The v5e trace predates the program's spans: the benchmark's own
    reduction reads what it read, and the program's comes out empty."""
    old = trace.reduce_trace(TESTDATA)
    assert old["modules"]["jit_train_step"]["count"] == 6
    assert set(old["idle_by_label"]) <= {"input_wait", "dispatch", "other"}
    assert trace.breakdown(old)["idle_gaps"][0][0] in trace.GAP_LABELS
    host, devices, program = program_spans.load_events(TESTDATA)
    assert program == {}
    assert (host, devices) == trace.load_events(TESTDATA)
    new = program_spans.reduce_events(host, devices, program)
    assert new["window_s"] == old["window_s"]
    assert new["program_spans"] == {} and new["idle_by_program_span"] == {}
    assert new["idle_staging_s"] is None
    assert new["gaps"] and all(g["spans"] == {} for g in new["gaps"])
    lo, hi = program_spans.window(host)
    idle = sum(b - a for dev in devices.values()
               for a, b in program_spans.idle_intervals(dev, lo, hi)) / 1e9
    assert idle == pytest.approx(old["window_s"] - old["busy_s"], rel=1e-9)


def _rec(start, end):
    return {"counters": {"start": start, "end": end}}


@pytest.mark.parametrize("name, counters, want", [
    ("host_read_ms_per_image",
     {"stage.read.busy_s": (1.0, 1.5), "worker.rows_decoded": (0, 10_000)},
     0.05),
    ("commit_wait_ms",
     {"stage.commit.busy_s": (0.5, 2.5), "loader.batches_delivered": (6, 106)},
     20.0),
    ("commit_wait_ms",   # commits off: the stage is there and stays at 0
     {"stage.commit.busy_s": (0.0, 0.0), "loader.batches_delivered": (6, 106)},
     0.0),
    ("pack_ms_per_step",
     {"stage.pack.busy_s": (0.1, 0.2), "loader.batches_delivered": (0, 50)},
     2.0),
])
def test_stage_readers(name, counters, want):
    start = {k: a for k, (a, _) in counters.items()}
    end = {k: b for k, (_, b) in counters.items()}
    assert _metric(name).read(_rec(start, end)) == pytest.approx(want)
    # a program without the stage (the parent of the change that added it)
    stage = next(k for k in counters if k.startswith("stage."))
    for side in (start, end):
        side.pop(stage)
    assert _metric(name).read(_rec(start, end)) is None
    # an untraced run records no counters
    assert _metric(name).read({"window": {}}) is None


def test_idle_staging_reader_finds_the_runs_trace(tmp_path, monkeypatch):
    reader = _metric("idle_staging_pct")
    host, dev, program = _events()
    reduced = program_spans.reduce_events(host, dev, program)
    monkeypatch.setattr(reader, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_REDUCED", {})
    rec = {"config": {"name": "cfg"},
           "trace": {"window_s": reduced["window_s"]}}
    assert reader.read(rec) is None          # no trace written
    assert reader.read({"config": {"name": "cfg"}}) is None   # untraced
    d = tmp_path / ".runs" / "cfg.mix" / "trace" / "plugins" / "profile"
    d.mkdir(parents=True)
    path = str(d / "host.xplane.pb")
    open(path, "w").close()

    def reduce_trace(p):
        assert p == path
        return reduced

    monkeypatch.setattr(program_spans, "reduce_trace", reduce_trace)
    assert reader.read(rec) == pytest.approx(25.0)
    # another run's trace: its window is not the record's
    rec["trace"]["window_s"] = 0.2
    assert reader.read(rec) is None
    # a program without the spans
    monkeypatch.setattr(program_spans, "_REDUCED", {})
    bare = program_spans.reduce_events(host, dev, {})
    monkeypatch.setattr(program_spans, "reduce_trace", lambda p: bare)
    rec["trace"]["window_s"] = bare["window_s"]
    assert reader.read(rec) is None


def test_new_metrics_are_declared_for_their_cells():
    cells = {w: spec.load_cell(w) for w in ("imagenet_rn50.hybrid",
                                            "imagenet_rn50.dp4",
                                            "tokens_gpt2.packed")}
    image = {"host_read_ms_per_image", "commit_wait_ms", "idle_staging_pct"}
    for w in ("imagenet_rn50.hybrid", "imagenet_rn50.dp4"):
        names = {m.name for m in cells[w].per_layer}
        assert image <= names and "pack_ms_per_step" not in names
    names = {m.name for m in cells["tokens_gpt2.packed"].per_layer}
    assert "pack_ms_per_step" in names and not image & names
    for m in cells["imagenet_rn50.hybrid"].per_layer:
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           m.name + ".py"))
