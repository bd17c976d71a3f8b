"""Telemetry subsystem tests: instrument semantics, thread safety, Chrome
trace schema, zero-cost-when-disabled, and end-to-end pipeline consistency.

The e2e test is the acceptance gate for the subsystem: a real ``make_reader``
run with telemetry enabled must produce non-zero decode spans AND yield
exactly the same rows as an untelemetered run (observing the pipeline must
never change what it delivers).
"""

import json
import os
import queue
import threading
import time

import numpy as np
import pytest

from petastorm_tpu import telemetry as T
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.test_util.synthetic import create_test_dataset


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("telemetry") / "ds")
    rows = create_test_dataset(path, num_rows=60, row_group_size_rows=10)
    return path, rows


# -- instrument semantics -----------------------------------------------------

def test_counter_semantics():
    tele = T.Telemetry()
    c = tele.counter("c")
    c.add()
    c.add(2.5)
    assert c.value == 3.5
    assert tele.counter("c") is c  # get-or-create returns the same object


def test_gauge_semantics():
    tele = T.Telemetry()
    g = tele.gauge("depth")
    assert g.value == 0.0
    g.set(7)
    g.set(3)
    assert g.value == 3.0


def test_histogram_semantics():
    tele = T.Telemetry()
    h = tele.histogram("lat", buckets=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.5, 5.0, 100.0):
        h.record(v)
    snap = h.snapshot()
    assert snap["buckets"] == [0.1, 1.0, 10.0]
    assert snap["counts"] == [1, 2, 1, 1]  # last bucket = overflow
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(106.05)
    assert h.mean == pytest.approx(106.05 / 5)
    assert h.quantile(0.5) == 1.0


def test_histogram_rejects_bad_buckets():
    tele = T.Telemetry()
    with pytest.raises(ValueError):
        tele.histogram("bad", buckets=[1.0, 0.1])
    with pytest.raises(ValueError):
        tele.histogram("empty", buckets=[])


def test_counter_thread_safety():
    tele = T.Telemetry()
    c = tele.counter("bumped")
    h = tele.histogram("observed", buckets=[0.5])
    n_threads, n_iter = 8, 5000

    def bump():
        for _ in range(n_iter):
            c.add()
            h.record(0.1)

    threads = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    assert h.snapshot()["counts"][0] == n_threads * n_iter


def test_stage_timer_feeds_counters_histogram_and_trace():
    tele = T.Telemetry()
    with tele.stage("decode", ordinal=7):
        time.sleep(0.01)
    snap = tele.snapshot()
    assert snap["counters"]["stage.decode.count"] == 1
    assert snap["counters"]["stage.decode.busy_s"] >= 0.01
    assert snap["histograms"]["stage.decode.latency_s"]["count"] == 1
    [event] = [e for e in tele.chrome_trace()["traceEvents"]
               if e.get("ph") == "X"]
    assert event["name"] == "decode"
    assert event["args"] == {"ordinal": 7}


# -- zero-cost-when-disabled --------------------------------------------------

def test_null_telemetry_is_default_and_noop(monkeypatch):
    monkeypatch.delenv(T.ENV_VAR, raising=False)
    tele = T.resolve(None)
    assert tele is T.NULL_TELEMETRY
    assert not tele.enabled
    # every span/stage call returns ONE shared do-nothing context manager
    assert tele.stage("decode") is tele.span("x") is T.NULL_CONTEXT
    tele.counter("c").add(5)
    assert tele.counter("c").value == 0
    assert tele.snapshot() == {}
    assert tele.chrome_trace() == {"traceEvents": []}
    assert "disabled" in tele.pipeline_report()


def test_env_var_enables_process_default(monkeypatch):
    monkeypatch.setenv(T.ENV_VAR, "1")
    tele = T.resolve(None)
    assert tele.enabled
    assert T.resolve(None) is tele       # process-wide singleton
    assert T.resolve(True) is tele
    monkeypatch.setenv(T.ENV_VAR, "0")
    assert T.resolve(None) is T.NULL_TELEMETRY
    assert T.resolve(False) is T.NULL_TELEMETRY


def test_reader_defaults_to_null_recorder(dataset, monkeypatch):
    monkeypatch.delenv(T.ENV_VAR, raising=False)
    url, _ = dataset
    with make_batch_reader(url, reader_pool_type="serial",
                           shuffle_row_groups=False) as reader:
        assert reader.telemetry is T.NULL_TELEMETRY
        next(iter(reader))


# -- Chrome trace export ------------------------------------------------------

def test_chrome_trace_schema(tmp_path):
    tele = T.Telemetry()
    with tele.stage("decode", path="a.parquet", rowgroup=3):
        pass
    with tele.span("custom", cat="io"):
        pass
    out = tmp_path / "trace.json"
    tele.export_chrome_trace(str(out))
    with open(out) as f:
        trace = json.load(f)
    assert "traceEvents" in trace
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(spans) == 2
    for e in spans:
        for key in ("ts", "dur", "tid", "pid", "name", "cat"):
            assert key in e, f"span missing {key}: {e}"
        assert isinstance(e["ts"], float) and e["ts"] >= 0
        assert isinstance(e["dur"], float) and e["dur"] >= 0
    # thread attribution: this thread's name rides a thread_name metadata event
    assert any(m["name"] == "thread_name"
               and m["args"]["name"] == threading.current_thread().name
               for m in metas)
    cats = {e["cat"] for e in spans}
    assert cats == {"stage", "io"}


def test_trace_buffer_bounded():
    tele = T.Telemetry(max_trace_events=5)
    for i in range(9):
        with tele.stage("s"):
            pass
    snap = tele.snapshot()
    assert snap["trace_events"] == 5
    assert snap["trace_dropped"] == 4
    # counters keep counting even once the trace buffer is full
    assert snap["counters"]["stage.s.count"] == 9


# -- pipeline report ----------------------------------------------------------

def test_pipeline_report_names_dominant_stage():
    tele = T.Telemetry()
    with tele.stage("decode"):
        time.sleep(0.02)
    with tele.stage("transform"):
        pass
    tele.counter("queue.results_empty_wait_s").add(0.5)
    report = tele.pipeline_report()
    assert "dominant stage: decode" in report
    assert "consumer starved on empty results queue" in report
    assert T.dominant_stage(tele.snapshot()) == "decode"


def test_report_renders_from_json_roundtripped_snapshot():
    # the --isolated benchmark path renders a report from a CHILD's snapshot
    # that crossed a JSON boundary; the renderer must not rely on live objects
    tele = T.Telemetry()
    with tele.stage("ventilate"):
        pass
    snap = json.loads(json.dumps(tele.snapshot()))
    assert "dominant stage: ventilate" in T.render_pipeline_report(snap)


# -- cache counters -----------------------------------------------------------

def test_inmemory_cache_hit_miss_counters():
    from petastorm_tpu.cache import InMemoryCache

    tele = T.Telemetry()
    cache = InMemoryCache(telemetry=tele)
    cache.get("k", lambda: np.zeros(4))
    cache.get("k", lambda: np.zeros(4))
    cache.get("k2", lambda: np.zeros(4))
    snap = tele.snapshot()
    assert snap["counters"]["cache.misses"] == 2
    assert snap["counters"]["cache.hits"] == 1


def test_local_disk_cache_counters_and_pickling(tmp_path):
    import pickle

    from petastorm_tpu.cache import LocalDiskCache

    tele = T.Telemetry()
    cache = LocalDiskCache(str(tmp_path / "c"), telemetry=tele)
    cache.get("k", lambda: 1)
    cache.get("k", lambda: 1)
    snap = tele.snapshot()
    assert snap["counters"]["cache.misses"] == 1
    assert snap["counters"]["cache.hits"] == 1
    # process-pool transport: the live recorder must not travel in the pickle
    clone = pickle.loads(pickle.dumps(cache))
    assert clone._telemetry is not tele
    assert clone.get("k", lambda: 2) == 1  # same backing dir, still works


# -- serial pool stall warning (satellite) ------------------------------------

def test_serial_executor_warns_on_wedged_work_item(monkeypatch, caplog):
    import logging

    from petastorm_tpu.pool import SerialExecutor

    monkeypatch.setenv("PETASTORM_TPU_STALL_WARN_S", "0.1")
    ex = SerialExecutor()
    ex.start(lambda: (lambda item: time.sleep(0.35)))
    ex.put("slow-item")
    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.pool"):
        ex.get(timeout=0.5)
    ex.stop()
    ex.join()
    assert any("has run for" in r.message for r in caplog.records)


def test_serial_executor_no_warning_when_fast(monkeypatch, caplog):
    import logging

    from petastorm_tpu.pool import SerialExecutor

    monkeypatch.setenv("PETASTORM_TPU_STALL_WARN_S", "30")
    ex = SerialExecutor()
    ex.start(lambda: (lambda item: item))
    ex.put("x")
    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.pool"):
        assert ex.get(timeout=0.5) == "x"
    ex.stop()
    ex.join()
    assert not [r for r in caplog.records if "has run for" in r.message]


def test_ventilate_stage_excludes_queue_full_wait():
    # a consumer-bound pipeline must NOT crown 'ventilate' the dominant
    # stage: time the ventilator spends blocked on a full input queue is
    # queue.input_full_wait_s, not ventilate busy time
    from petastorm_tpu.pool import ThreadedExecutor, Ventilator

    class _Plan:
        def epoch_items(self, epoch):
            return list(range(6))

        def total_items(self, num_epochs):
            return 6 * num_epochs

    tele = T.Telemetry()
    ex = ThreadedExecutor(workers_count=1, results_queue_size=1,
                          in_queue_size=1, telemetry=tele)
    ex.start(lambda: (lambda item: time.sleep(0.06) or item))
    vent = Ventilator(ex, _Plan(), num_epochs=1, telemetry=tele)
    vent.start()
    got = 0
    deadline = time.monotonic() + 20
    while got < 6 and time.monotonic() < deadline:
        try:
            ex.get(timeout=0.5)
            got += 1
        except queue.Empty:
            continue
    vent.stop()
    vent.join()
    ex.stop()
    ex.join()
    assert got == 6
    counters = tele.snapshot()["counters"]
    # the slow worker backs the 1-slot input queue up: most put time is
    # blocked wait, and ventilate busy must exclude it
    assert counters["queue.input_full_wait_s"] > 0.1
    assert (counters["stage.ventilate.busy_s"]
            < 0.5 * counters["queue.input_full_wait_s"])


# -- end-to-end ---------------------------------------------------------------

@pytest.mark.parametrize("pool", ["serial", "thread"])
def test_e2e_telemetered_run_matches_untelemetered(dataset, pool):
    url, rows = dataset
    expected_ids = {r["id"] for r in rows}

    with make_reader(url, reader_pool_type=pool, workers_count=2,
                     shuffle_row_groups=False) as reader:
        plain_ids = {r.id for r in reader}

    tele = T.Telemetry()
    with make_reader(url, reader_pool_type=pool, workers_count=2,
                     shuffle_row_groups=False, telemetry=tele) as reader:
        assert reader.telemetry is tele
        traced_ids = {r.id for r in reader}

    assert plain_ids == traced_ids == expected_ids

    snap = tele.snapshot()
    counters = snap["counters"]
    # non-zero decode spans with real durations
    assert counters["stage.decode.count"] == 6        # 60 rows / 10 per group
    assert counters["stage.decode.busy_s"] > 0
    assert counters["worker.rowgroups_decoded"] == 6
    assert counters["worker.rows_decoded"] == 60
    assert counters["reader.rows_emitted"] == 60
    assert counters["reader.batches_consumed"] == 6
    assert snap["histograms"]["stage.decode.latency_s"]["count"] == 6
    # the trace carries the decode spans with worker-thread attribution
    trace = tele.chrome_trace()
    decode_spans = [e for e in trace["traceEvents"]
                    if e.get("ph") == "X" and e["name"] == "decode"]
    assert len(decode_spans) == 6
    assert all(e["dur"] > 0 for e in decode_spans)
    report = tele.pipeline_report()
    assert "dominant stage:" in report


def test_e2e_transform_stage_recorded(dataset):
    from petastorm_tpu.transform import TransformSpec

    url, _ = dataset
    tele = T.Telemetry()
    spec = TransformSpec(lambda cols: {"id": cols["id"] * 2},
                         edit_fields=[], removed_fields=[
                             f for f in ("id2", "partition_key",
                                         "python_primitive_uint8", "image_png",
                                         "matrix", "matrix_compressed",
                                         "matrix_var", "sensor_name",
                                         "timestamp_s", "nullable_float")])
    with make_batch_reader(url, reader_pool_type="thread", workers_count=2,
                           shuffle_row_groups=False, transform_spec=spec,
                           telemetry=tele) as reader:
        total = sum(b.num_rows for b in reader.iter_batches())
    assert total == 60
    counters = tele.snapshot()["counters"]
    assert counters["stage.transform.count"] == 6
    assert counters["stage.decode.count"] == 6


def test_diagnose_runs_and_exports_trace(dataset, tmp_path):
    from petastorm_tpu.tools.diagnose import run_diagnosis

    url, _ = dataset
    result = run_diagnosis(url, pool_type="thread", workers_count=2)
    assert result["rows"] == 60
    assert result["batches"] == 6
    assert result["dominant_stage"]
    assert "dominant stage:" in result["report"]
    out = tmp_path / "trace.json"
    result["telemetry"].export_chrome_trace(str(out))
    with open(out) as f:
        trace = json.load(f)
    assert any(e.get("name") == "decode" for e in trace["traceEvents"])


def test_diagnose_cli_json_synthetic(capsys):
    from petastorm_tpu.tools import diagnose

    rc = diagnose.main(["--synthetic", "--rows", "30",
                        "--row-group-size", "10", "--json",
                        "--pool-type", "serial"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["rows"] == 30
    assert out["dominant_stage"]
    assert out["snapshot"]["counters"]["stage.decode.count"] == 3


def test_benchmark_result_carries_metrics(dataset):
    from petastorm_tpu.benchmark.throughput import reader_throughput

    url, _ = dataset
    result = reader_throughput(url, read_method="batch", warmup_cycles=1,
                               measure_cycles=3, pool_type="serial",
                               workers_count=1, shuffle_row_groups=False,
                               telemetry=T.Telemetry())
    assert result.metrics is not None
    assert result.metrics["counters"]["stage.decode.count"] > 0
    # and the JSON line round-trips with metrics attached
    assert json.loads(result.to_json())["metrics"]["counters"]


def test_jax_loader_records_transfer_stages(dataset):
    from petastorm_tpu.jax.loader import JaxDataLoader

    url, _ = dataset
    tele = T.Telemetry()
    reader = make_batch_reader(url, reader_pool_type="thread", workers_count=2,
                               shuffle_row_groups=False, telemetry=tele,
                               schema_fields=["id", "matrix"])
    with JaxDataLoader(reader, batch_size=10) as loader:
        assert loader.telemetry is tele   # inherited from the reader
        delivered = sum(int(b["id"].shape[0]) for b in loader)
    assert delivered == 60
    counters = tele.snapshot()["counters"]
    assert counters["stage.host-prep.count"] > 0
    assert counters["stage.device-transfer.count"] == 6
    assert counters["stage.device-transfer.busy_s"] > 0
    assert counters["loader.batches_delivered"] == 6


# -- spans on the profiler's clock ---------------------------------------------


def _profiled_spans(trace_dir):
    """``petastorm/*`` events of the host plane of the one xplane under
    ``trace_dir``: [(line index, name, start_ns, end_ns, stats)]."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(T.PROFILER_PREFIX):
                    out.append((i, e.name[len(T.PROFILER_PREFIX):],
                                e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def test_spans_land_in_a_running_profiler_session(tmp_path):
    jax = pytest.importorskip("jax")
    tele = T.Telemetry()

    def worker():
        # a path is no id: it stays on the host clock's span only
        with tele.stage("decode", path="/d/a,b#c.parquet", ordinal=7,
                        rowgroup=2):
            with tele.stage("read", ordinal=7, rowgroup=2):
                time.sleep(0.02)
            time.sleep(0.01)

    def transfer():
        with tele.stage("device-transfer", batch=np.int64(3)):
            with tele.span("put", batch=3):
                time.sleep(0.005)
            with tele.stage("commit", batch=3):
                time.sleep(0.02)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tele.stage("host-prep", batch=3):
            time.sleep(0.005)
        threads = [threading.Thread(target=worker, name="w"),
                   threading.Thread(target=transfer, name="t")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        jax.profiler.stop_trace()
    spans = {name: (line, a, b, stats)
             for line, name, a, b, stats in _profiled_spans(tmp_path)}
    assert set(spans) == {"host-prep", "decode", "read", "device-transfer",
                          "put", "commit"}
    line = {name: s[0] for name, s in spans.items()}
    # each span on the line of the thread that ran it
    assert line["read"] == line["decode"]
    assert line["put"] == line["commit"] == line["device-transfer"]
    assert len({line["host-prep"], line["decode"], line["commit"]}) == 3
    assert spans["decode"][3] == {"ordinal": 7, "rowgroup": 2}
    assert spans["read"][3] == {"ordinal": 7, "rowgroup": 2}
    assert spans["commit"][3] == {"batch": 3}
    assert spans["device-transfer"][3] == {"batch": 3}
    # the children sit inside their parents
    for child, parent in (("read", "decode"), ("commit", "device-transfer"),
                          ("put", "device-transfer")):
        assert spans[parent][1] <= spans[child][1]
        assert spans[child][2] <= spans[parent][2]
    # and each lasts what the host clock's span lasted
    host = {e["name"]: e["dur_ms"] for e in tele.trace.tail(20)}
    for name, (_, a, b, _) in spans.items():
        assert (b - a) / 1e6 == pytest.approx(host[name], abs=2.0), name
    # the counters are the stages' as ever
    counters = tele.snapshot()["counters"]
    assert counters["stage.read.count"] == counters["stage.commit.count"] == 1
    assert counters["stage.decode.busy_s"] >= counters["stage.read.busy_s"]


def test_null_telemetry_and_idle_profiler_emit_nothing(tmp_path):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with T.NULL_TELEMETRY.stage("decode", ordinal=1):
            with T.NULL_TELEMETRY.span("read", ordinal=1):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    assert _profiled_spans(tmp_path) == []
    # an enabled recorder with no session running opens no annotation
    assert T._profiler_mark("decode", {"ordinal": 1}) is None


def test_telemetry_import_leaves_jax_unimported():
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys\n"
            "import petastorm_tpu.telemetry as T\n"
            "t = T.Telemetry()\n"
            "with t.stage('decode', ordinal=1):\n"
            "    with t.span('read', ordinal=1):\n"
            "        pass\n"
            "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=root + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_jpeg_reader_fills_read_and_commit_stages(tmp_path):
    pytest.importorskip("jax")
    from petastorm_tpu.native import image as native_image

    if not native_image.available():
        pytest.skip("native image library unavailable")
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.schema import Field, Schema

    schema = Schema("Jpeg", [
        Field("idx", np.int64),
        Field("image", np.uint8, (32, 48, 3),
              CompressedImageCodec("jpeg", quality=90))])
    rng = np.random.default_rng(0)
    rows = [{"idx": i,
             "image": rng.integers(0, 255, (32, 48, 3), dtype=np.uint8)}
            for i in range(32)]
    url = str(tmp_path / "ds")
    write_dataset(url, schema, rows, row_group_size_rows=8)
    tele = T.Telemetry()
    with make_batch_reader(url, shuffle_row_groups=False, num_epochs=1,
                           decode_placement={"image": "device"},
                           reader_pool_type="thread", workers_count=2,
                           telemetry=tele) as r:
        with JaxDataLoader(r, batch_size=8, fields=["idx", "image"],
                           transfer_commit=True) as loader:
            delivered = sum(int(b["idx"].shape[0]) for b in loader)
    assert delivered == 32
    c = tele.snapshot()["counters"]
    assert c["stage.read.count"] == c["stage.decode.count"] == 4
    assert 0 < c["stage.read.busy_s"] <= c["stage.decode.busy_s"]
    # the first commit rides the probe's warm-up; every batch commits
    assert c["stage.commit.count"] == c["loader.batches_delivered"] == 4
    assert 0 < c["stage.commit.busy_s"] <= c["stage.device-transfer.busy_s"]
    names = {e["name"] for e in tele.trace.tail(500)}
    assert {"device-decode", "put", "commit", "results-empty"} <= names
    batches = {e["args"]["batch"] for e in tele.trace.tail(500)
               if e["name"] == "commit"}
    assert batches == {0, 1, 2, 3}


def test_commit_stage_reads_zero_with_commits_off(dataset):
    pytest.importorskip("jax")
    from petastorm_tpu.jax.loader import JaxDataLoader

    url, _ = dataset
    tele = T.Telemetry()
    reader = make_batch_reader(url, reader_pool_type="serial",
                               shuffle_row_groups=False, telemetry=tele,
                               schema_fields=["id"])
    with JaxDataLoader(reader, batch_size=10,
                       transfer_commit=False) as loader:
        assert sum(1 for _ in loader) == 6
    c = tele.snapshot()["counters"]
    assert c["stage.commit.count"] == 0 and c["stage.commit.busy_s"] == 0
    assert c["stage.read.count"] == c["stage.decode.count"] > 0


def test_packed_loader_fills_pack_stage(tmp_path):
    pytest.importorskip("jax")
    from petastorm_tpu.sequence import make_packed_sequence_loader
    from petastorm_tpu.test_util.synthetic import write_token_corpus

    url = str(tmp_path / "corpus")
    write_token_corpus(url, n_docs=60, rows_per_rg=10, mean_len=20,
                       max_len=80, seed=5)
    tele = T.Telemetry()
    with make_packed_sequence_loader(url, batch_size=4, seq_len=64, seed=3,
                                     workers_count=2,
                                     telemetry=tele) as loader:
        steps = sum(1 for _ in loader)
    c = tele.snapshot()["counters"]
    assert steps == c["loader.batches_delivered"] > 0
    # one pack span per document fed
    assert c["stage.pack.count"] == c["sequence.docs_packed"] == 60
    assert c["stage.pack.busy_s"] > 0
    assert c["stage.read.count"] == c["stage.decode.count"] == 6
    assert c["stage.decode.busy_s"] >= c["stage.read.busy_s"]
