"""Live observability layer: metrics sampler, flight recorder, Prometheus
export endpoint, JSONL sink and ``diagnose --watch``.

The e2e acceptance test mirrors the PR gate: a chaos-induced permanent hang
with no ``item_deadline_s`` must abort with ``PipelineStallError`` AND leave
a flight-recorder artifact whose sampled series show the consumer queue-wait
rising across consecutive intervals - the crash artifact alone is sufficient
to diagnose the stall.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from petastorm_tpu import telemetry as T
from petastorm_tpu.errors import ErrorBudgetExceededError, ErrorPolicy
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.pool import PipelineStallError, WorkerError
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.telemetry.export import (MetricsExportServer,
                                            render_prometheus, write_jsonl)
from petastorm_tpu.telemetry.sampler import (MetricsSampler,
                                             load_flight_records)
from petastorm_tpu.test_util.chaos import ChaosSpec


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    url = str(tmp_path_factory.mktemp("obs") / "ds")
    schema = Schema("Obs", [Field("x", np.int64)])
    write_dataset(url, schema, [{"x": i} for i in range(60)],
                  row_group_size_rows=10)
    return url


# -- MetricsSampler -----------------------------------------------------------

def test_sampler_counter_deltas_become_rates():
    tele = T.Telemetry()
    c = tele.counter("reader.rows_emitted")
    s = MetricsSampler(tele, interval_s=10.0)  # manual sampling only
    s.start()
    c.add(100)
    time.sleep(0.05)
    point = s.sample_now()
    assert point is not None
    # 100 counts over the measured dt -> rate = 100/dt
    assert point["rates"]["reader.rows_emitted"] == pytest.approx(
        100 / point["dt_s"])
    assert point["counters"]["reader.rows_emitted"] == 100
    # second interval with no activity -> rate drops to 0
    time.sleep(0.02)
    point2 = s.sample_now()
    assert point2["rates"]["reader.rows_emitted"] == 0.0
    s.stop()


def test_sampler_stage_interval_percentiles():
    tele = T.Telemetry()
    s = MetricsSampler(tele, interval_s=10.0)
    s.start()
    for _ in range(5):
        tele.record_stage("decode", 0, int(0.008e9))  # 8 ms -> 0.01 bucket
    time.sleep(0.02)
    p1 = s.sample_now()
    assert p1["stages"]["decode"]["p50_s"] == pytest.approx(0.01)
    # next interval records only slow ops: the INTERVAL p50 must reflect
    # them, not the cumulative mix
    for _ in range(5):
        tele.record_stage("decode", 0, int(0.8e9))    # 0.8 s -> 1.0 bucket
    time.sleep(0.02)
    p2 = s.sample_now()
    assert p2["stages"]["decode"]["p50_s"] == pytest.approx(1.0)
    # an idle interval yields None percentiles, zero rate
    time.sleep(0.02)
    p3 = s.sample_now()
    assert p3["stages"]["decode"]["p50_s"] is None
    assert p3["stages"]["decode"]["rate_per_s"] == 0.0
    s.stop()


def test_sampler_ring_is_bounded_and_tail_windows():
    tele = T.Telemetry()
    s = MetricsSampler(tele, interval_s=10.0, max_points=5)
    s.start()
    for _ in range(9):
        time.sleep(0.011)
        s.sample_now()
    assert len(s) == 5
    series = s.series()
    assert [p["t"] for p in series] == sorted(p["t"] for p in series)
    assert s.latest() == series[-1]
    # a tiny window keeps only the newest points
    assert len(s.tail(0.0)) >= 1
    assert len(s.tail(1e9)) == 5
    s.stop()


def test_sampler_over_null_telemetry_is_inert():
    s = MetricsSampler(T.NULL_TELEMETRY)
    s.start()
    assert not s.enabled
    assert s.sample_now() is None
    assert s.series() == [] and s.latest() is None
    s.stop()


def test_sampler_thread_safety_under_concurrent_recording():
    # test_concurrency_stress.py pattern: hammer the registry from N threads
    # while the sampler ticks fast; totals must be exact and every sampled
    # point internally consistent (no torn reads, no exceptions)
    tele = T.Telemetry()
    s = MetricsSampler(tele, interval_s=0.005)
    s.start()
    c = tele.counter("bumped")
    n_threads, n_iter = 8, 3000

    def bump():
        h = tele.histogram("stage.decode.latency_s")
        for i in range(n_iter):
            c.add()
            h.record(0.001 * (i % 7))
            tele.counter("stage.decode.count").add()

    threads = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    time.sleep(0.02)
    s.sample_now()
    s.stop()
    assert c.value == n_threads * n_iter
    points = s.series()
    assert points, "sampler recorded nothing under load"
    for p in points:
        assert p["dt_s"] > 0
        for rate in p["rates"].values():
            assert rate >= 0.0
    # the series totals are monotonic (counters never run backwards)
    totals = [p["counters"].get("bumped", 0.0) for p in points]
    assert totals == sorted(totals)
    assert totals[-1] == n_threads * n_iter


# -- Prometheus exposition (golden) -------------------------------------------

def test_prometheus_exposition_golden():
    # format gate: names, labels and types are a scrape contract - renderer
    # changes must show up here as a deliberate diff
    tele = T.Telemetry()
    tele.counter("errors.skipped_rowgroups").add(2)
    tele.gauge("pool.results_queue_depth").set(3)
    tele.histogram("stage.decode.latency_s", buckets=[0.01, 0.1, 1.0])
    for _ in range(4):
        tele.record_stage("decode", 0, int(0.05e9))  # 50 ms -> 0.1 bucket
    snap = tele.snapshot()
    snap["uptime_s"] = 12.5  # pin the one non-deterministic value
    # stage histogram was created with custom buckets; busy_s is whatever
    # perf accumulated - pin it too for the golden comparison
    snap["counters"]["stage.decode.busy_s"] = 0.2
    text = render_prometheus(snap)
    assert text == """\
# HELP petastorm_tpu_uptime_seconds Seconds since this pipeline's telemetry registry was created.
# TYPE petastorm_tpu_uptime_seconds gauge
petastorm_tpu_uptime_seconds 12.5
# HELP petastorm_tpu_errors_skipped_rowgroups_total Cumulative total of errors.skipped_rowgroups.
# TYPE petastorm_tpu_errors_skipped_rowgroups_total counter
petastorm_tpu_errors_skipped_rowgroups_total 2
# HELP petastorm_tpu_pool_results_queue_depth Last observed value of pool.results_queue_depth.
# TYPE petastorm_tpu_pool_results_queue_depth gauge
petastorm_tpu_pool_results_queue_depth 3
# HELP petastorm_tpu_stage_busy_seconds_total Cumulative busy seconds per pipeline stage.
# TYPE petastorm_tpu_stage_busy_seconds_total counter
petastorm_tpu_stage_busy_seconds_total{stage="decode"} 0.2
# HELP petastorm_tpu_stage_ops_total Cumulative executions per pipeline stage.
# TYPE petastorm_tpu_stage_ops_total counter
petastorm_tpu_stage_ops_total{stage="decode"} 4
# HELP petastorm_tpu_stage_latency_seconds Cumulative stage latency quantiles (fixed-bucket upper bounds).
# TYPE petastorm_tpu_stage_latency_seconds gauge
petastorm_tpu_stage_latency_seconds{stage="decode",quantile="0.5"} 0.1
petastorm_tpu_stage_latency_seconds{stage="decode",quantile="0.99"} 0.1
"""


def test_prometheus_includes_sampler_interval_series():
    tele = T.Telemetry()
    s = MetricsSampler(tele, interval_s=10.0)
    s.start()
    tele.record_stage("decode", 0, int(0.008e9))
    time.sleep(0.02)
    s.sample_now()
    text = render_prometheus(tele.snapshot(), sampler_point=s.latest())
    assert 'petastorm_tpu_stage_rate_per_second{stage="decode"}' in text
    assert ('petastorm_tpu_stage_interval_latency_seconds'
            '{stage="decode",quantile="0.99"}') in text
    assert "petastorm_tpu_sample_interval_seconds" in text
    s.stop()


def test_metrics_export_server_serves_and_404s():
    tele = T.Telemetry()
    tele.counter("liveness.hung_workers_killed").add(1)
    server = MetricsExportServer(tele, port=0)
    port = server.start()
    assert port and server.port == port
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "petastorm_tpu_liveness_hung_workers_killed_total 1" in body
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/other",
                                   timeout=5)
        assert err.value.code == 404
    finally:
        server.stop()
    assert server.port == port  # survives stop for post-mortem diagnostics


def test_write_jsonl_push_sink(tmp_path):
    tele = T.Telemetry()
    s = MetricsSampler(tele, interval_s=10.0)
    s.start()
    tele.counter("reader.rows_emitted").add(10)
    time.sleep(0.02)
    s.sample_now()
    out = tmp_path / "series.jsonl"
    write_jsonl(s.series(), str(out))
    write_jsonl(s.series(), str(out))  # append mode
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert len(lines) == 2
    assert all(ln["counters"]["reader.rows_emitted"] == 10 for ln in lines)
    s.stop()


# -- pipeline report: registered-but-unsampled stages -------------------------

def test_report_renders_no_samples_yet_instead_of_omitting():
    tele = T.Telemetry()
    tele.register_stage("decode")
    report = tele.pipeline_report()
    assert "decode" in report
    assert "(no samples yet)" in report
    assert T.dominant_stage(tele.snapshot()) == ""
    # once another stage records, IT is dominant; decode still renders
    with tele.stage("transform"):
        time.sleep(0.005)
    report = tele.pipeline_report()
    assert "dominant stage: transform" in report
    assert "(no samples yet)" in report
    assert T.dominant_stage(tele.snapshot()) == "transform"


# -- reader integration -------------------------------------------------------

def test_reader_serves_metrics_and_latches_final_snapshot(dataset):
    with make_batch_reader(dataset, reader_pool_type="thread",
                           workers_count=2, shuffle_row_groups=False,
                           metrics_port=0, sample_interval_s=0.1) as reader:
        assert reader.telemetry.enabled  # auto-enabled by metrics_port
        port = reader.metrics_server.port
        rows = sorted(x for b in reader.iter_batches()
                      for x in b.columns["x"])
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    assert rows == list(range(60))
    assert 'stage="decode"' in body
    assert "petastorm_tpu_liveness_hung_workers_killed_total" in body
    # final snapshot attached on the clean close path
    diag = reader.diagnostics
    assert diag["telemetry"]["counters"]["reader.rows_emitted"] == 60
    assert diag["metrics_port"] == port
    assert len(reader.sampler.series()) >= 1


def test_reader_final_snapshot_on_failure_close(dataset):
    tele = T.Telemetry()
    chaos = ChaosSpec(decode_fail_ordinals=(1,))
    with pytest.raises(WorkerError):
        with make_batch_reader(dataset, reader_pool_type="thread",
                               workers_count=2, shuffle_row_groups=False,
                               chaos=chaos, telemetry=tele,
                               sample_interval_s=0.1) as reader:
            for _ in reader.iter_batches():
                pass
    # the raise-mode failure still latched counters + a flight record
    diag = reader.diagnostics
    assert "telemetry" in diag
    assert diag["flight_recorder"]["reason"].startswith("WorkerError")


def test_error_budget_exhaustion_carries_diagnostics(dataset):
    chaos = ChaosSpec(decode_fail_rate=1.0)
    with pytest.raises(ErrorBudgetExceededError) as err:
        with make_batch_reader(dataset, reader_pool_type="thread",
                               workers_count=2, shuffle_row_groups=False,
                               chaos=chaos, sample_interval_s=0.1,
                               on_error=ErrorPolicy(
                                   max_skipped_rowgroups=1)) as reader:
            for _ in reader.iter_batches():
                pass
    diag = err.value.diagnostics
    assert diag["skipped_rowgroups"] == 2
    assert diag["flight_recorder"]["reason"].startswith(
        "ErrorBudgetExceededError")


def test_flight_recorder_e2e_stall_series_show_rising_queue_wait(
        dataset, tmp_path):
    """Acceptance: permanent hangs, no item_deadline_s -> PipelineStallError
    whose JSONL flight record alone shows the consumer queue-wait rising
    across >= 3 consecutive intervals before the abort."""
    rec_path = str(tmp_path / "flight.jsonl")
    chaos = ChaosSpec(hang_ordinals=(1, 2), hang_s=600)
    with pytest.raises(PipelineStallError) as err:
        with make_batch_reader(dataset, reader_pool_type="thread",
                               workers_count=2, shuffle_row_groups=False,
                               chaos=chaos, stall_warn_s=0,
                               stall_abort_s=3.5,
                               flight_record_path=rec_path,
                               sample_interval_s=0.6) as reader:
            for _ in reader.iter_batches():
                pass
    # the record rides the raised error's diagnostics...
    fr = err.value.diagnostics["flight_recorder"]
    assert fr["reason"].startswith("PipelineStallError")
    assert len(fr["points"]) >= 2
    # ...and the JSONL artifact alone is sufficient to diagnose the stall
    [record] = load_flight_records(rec_path)
    waits = [p["counters"].get("queue.results_empty_wait_s", 0.0)
             for p in record["points"]]
    streak, best = 0, 0
    for a, b in zip(waits, waits[1:]):
        streak = streak + 1 if b > a else 0
        best = max(best, streak)
    assert best >= 3, f"queue-wait series not rising: {waits}"
    assert record["trace_tail"], "flight record carries no trace tail"
    assert record["final"]["counters"]["reader.batches_consumed"] == 1


def test_env_var_flight_record_and_metrics_port(dataset, tmp_path,
                                                monkeypatch):
    rec = tmp_path / "env_flight.jsonl"
    monkeypatch.setenv("PETASTORM_TPU_FLIGHT_RECORD", str(rec))
    monkeypatch.setenv("PETASTORM_TPU_METRICS_PORT", "0")
    monkeypatch.setenv("PETASTORM_TPU_SAMPLE_INTERVAL_S", "0.1")
    with make_batch_reader(dataset, reader_pool_type="serial",
                           shuffle_row_groups=False) as reader:
        assert reader.metrics_server is not None
        assert reader.sampler is not None
        assert reader.sampler.interval_s == pytest.approx(0.1)
        assert reader._flight_record_path == str(rec)
        total = sum(b.num_rows for b in reader.iter_batches())
    assert total == 60
    assert not rec.exists()  # clean run: no flight record dumped


# -- diagnose --watch ---------------------------------------------------------

def test_render_watch_frame_from_canned_point():
    from petastorm_tpu.tools.diagnose import render_watch_frame

    point = {
        "t": 5.0, "dt_s": 1.0,
        "counters": {"reader.rows_emitted": 500,
                     "errors.skipped_rowgroups": 2},
        "rates": {"reader.rows_emitted": 100.0,
                  "reader.batches_consumed": 10.0,
                  "queue.results_empty_wait_s": 0.8},
        "gauges": {"pool.results_queue_depth": 3.0},
        "stages": {"decode": {"count": 50, "rate_per_s": 10.0,
                              "busy_frac": 1.9, "p50_s": 0.01, "p99_s": 0.1},
                   "transform": {"count": 0, "rate_per_s": 0.0,
                                 "busy_frac": 0.0, "p50_s": None,
                                 "p99_s": None}},
    }
    diag = {"workers_busy": [(0, 7, 2.5)], "consumed_items": 49,
            "expected_items": 60, "requeued_items": 1, "hedged_items": 0,
            "hung_workers_killed": 0, "skipped_rowgroups": 2}
    frame = render_watch_frame(point, diag, elapsed_s=5.0)
    assert "rows/s:" in frame and "100.0" in frame
    assert "dominant stage (this interval): decode" in frame
    assert "(no samples yet)" in frame           # transform registered, idle
    assert "consumer starved" in frame
    assert "results_queue_depth=3" in frame
    assert "errors.skipped_rowgroups=2" in frame
    assert "oldest item 2.5s" in frame
    assert "consumed 49/60" in frame


def test_diagnose_watch_cli_bounded_by_duration(dataset, capsys):
    from petastorm_tpu.tools import diagnose

    rc = diagnose.main([dataset, "--watch", "--interval", "0.2",
                        "--duration", "6", "--workers-count", "2",
                        "--num-epochs", "0"])  # 0 = infinite; duration bounds
    out = capsys.readouterr().out
    assert rc == 0
    assert "petastorm-tpu watch" in out
    assert "watch finished" in out
    assert "dominant stage" in out


def test_diagnose_metrics_port_flag(dataset, capsys):
    from petastorm_tpu.tools.diagnose import run_diagnosis

    result = run_diagnosis(dataset, pool_type="serial", workers_count=1,
                           metrics_port=0, sample_interval_s=0.2)
    assert result["rows"] == 60
    assert result["metrics_port"]


def test_reader_warns_when_flight_record_requested_but_sampling_off(
        dataset, tmp_path, caplog):
    import logging

    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.reader"):
        with make_batch_reader(dataset, reader_pool_type="serial",
                               shuffle_row_groups=False,
                               flight_record_path=str(tmp_path / "fr.jsonl"),
                               sample_interval_s=0) as reader:
            assert reader.sampler is None
            next(reader.iter_batches())
    assert any("inert" in r.message for r in caplog.records)
