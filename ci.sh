#!/usr/bin/env bash
# One-command CI entry: build the native libraries, then run the full suite.
#
# Reference analog: /root/reference/docker/ (the reference's CI container) and
# its tox/pytest entry points. Here the native build is on-demand (g++ via
# petastorm_tpu.native.build, cached .so), so "build" is just forcing it once
# up front where a toolchain failure surfaces as a CI error instead of a
# silent host-decode fallback at test time.
set -euo pipefail
cd "$(dirname "$0")"

echo "== native build =="
python - <<'PY'
from petastorm_tpu.native import build
for name in ("image_decode", "shm_arena"):
    path = build.build(name, force=True)
    assert path, f"native build of {name} failed (see warnings above)"
    print(f"built {name}: {path}")
PY

echo "== test suite (8-device virtual CPU mesh; see tests/conftest.py) =="
# COV=1 ./ci.sh adds line coverage.
# Uses pytest-cov when installed, else the stdlib sys.monitoring collector
# (tools/run_coverage.py - coverage.py is uninstallable in the zero-egress
# build env). Runs inside docker/Dockerfile, which pins this toolchain
# (docker/environment.lock.md).
if [ "${COV:-0}" = "1" ]; then
    if python -c "import pytest_cov" 2>/dev/null; then
        python -m pytest tests/ -q --cov=petastorm_tpu --cov-report=term "$@"
    else
        python tools/run_coverage.py "$@"
    fi
else
    python -m pytest tests/ -q "$@"
fi

echo "== telemetry smoke (tools/diagnose.py on a synthetic dataset) =="
# a short telemetered read must render the bottleneck report, name a
# dominant stage, and export parseable Chrome trace_event JSON
JAX_PLATFORMS=cpu python - <<'PY'
import json, os, tempfile
from petastorm_tpu.tools.diagnose import main

trace_path = os.path.join(tempfile.mkdtemp(), "trace.json")
rc = main(["--synthetic", "--rows", "60", "--row-group-size", "10",
           "--trace-out", trace_path])
assert rc == 0, f"diagnose exited {rc}"
with open(trace_path) as f:
    trace = json.load(f)
spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
assert spans, "trace has no spans"
for key in ("ts", "dur", "tid", "pid", "name", "cat"):
    assert key in spans[0], f"span missing {key}"
assert any(e["name"] == "decode" for e in spans), "no decode spans"
print(f"telemetry smoke OK ({len(spans)} spans)")
PY

echo "== chaos smoke (fault-tolerant ingest under injected failures) =="
# a short read with one injected decode failure and one hard worker kill
# under on_error='skip' must COMPLETE (minus exactly the poisoned rowgroup)
# with the damage counted in telemetry - the degraded-not-dead contract
JAX_PLATFORMS=cpu python - <<'PY'
import tempfile
import numpy as np
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.telemetry import Telemetry
from petastorm_tpu.test_util.chaos import ChaosSpec

tmp = tempfile.mkdtemp(prefix="petastorm_tpu_chaos_smoke_")
schema = Schema("ChaosSmoke", [Field("x", np.int64)])
write_dataset(tmp, schema, [{"x": i} for i in range(60)],
              row_group_size_rows=10)
tele = Telemetry()
chaos = ChaosSpec(decode_fail_ordinals=(2,), kill_ordinals=(4,))
with make_batch_reader(tmp, reader_pool_type="thread", workers_count=2,
                       shuffle_row_groups=False, chaos=chaos,
                       on_error="skip", telemetry=tele) as reader:
    rows = sorted(x for b in reader.iter_batches() for x in b.columns["x"])
    diag = reader.diagnostics
assert rows == sorted(set(range(60)) - set(range(20, 30))), len(rows)
assert diag["skipped_rowgroups"] == 1, diag
assert diag["requeued_items"] == 1, diag
counters = tele.snapshot()["counters"]
assert counters["errors.skipped_rowgroups"] == 1
assert counters["errors.requeued_items"] == 1
print("chaos smoke OK (1 rowgroup quarantined, 1 kill requeued,"
      f" {len(rows)} healthy rows delivered)")
PY

echo "== hang-chaos smoke (liveness: hung workers killed + replaced, bounded time) =="
# two PERMANENTLY hung process workers + item_deadline_s: the run must
# COMPLETE with the exact row multiset and >= 2 hung-worker kills, inside a
# hard timeout - the wedged-pipeline-recovers contract.  Runs from a real
# file (not stdin): the process pool's spawn re-imports __main__.
HANG_SMOKE="$(mktemp /tmp/petastorm_tpu_hang_smoke_XXXXXX.py)"
cat > "$HANG_SMOKE" <<'PY'
import tempfile
import numpy as np
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.telemetry import Telemetry
from petastorm_tpu.test_util.chaos import ChaosSpec

if __name__ == "__main__":
    tmp = tempfile.mkdtemp(prefix="petastorm_tpu_hang_smoke_")
    schema = Schema("HangSmoke", [Field("x", np.int64)])
    write_dataset(tmp, schema, [{"x": i} for i in range(60)],
                  row_group_size_rows=10)
    tele = Telemetry()
    chaos = ChaosSpec(hang_ordinals=(1, 4), hang_s=600)
    with make_batch_reader(tmp, reader_pool_type="process", workers_count=2,
                           shuffle_row_groups=False, chaos=chaos,
                           item_deadline_s=2.0, telemetry=tele) as reader:
        rows = sorted(x for b in reader.iter_batches() for x in b.columns["x"])
        diag = reader.diagnostics
    assert rows == list(range(60)), len(rows)
    assert diag["hung_workers_killed"] >= 2, diag
    counters = tele.snapshot()["counters"]
    assert counters["liveness.hung_workers_killed"] >= 2
    print("hang-chaos smoke OK"
          f" ({diag['hung_workers_killed']} hung workers killed+replaced,"
          f" {diag['requeued_items']} items requeued,"
          f" {len(rows)} rows delivered exactly once)")
PY
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 120 python "$HANG_SMOKE"
rm -f "$HANG_SMOKE"

echo "== metrics endpoint smoke (ephemeral port scrape during a chaos read) =="
# a chaos read serving --metrics-port 0 must expose Prometheus series for the
# decode stage and the liveness fault counters on one scrape of the ephemeral
# endpoint - the live-observability contract (docs/operations.md "Live
# monitoring").  stdlib urllib stands in for curl (same GET, no extra dep).
JAX_PLATFORMS=cpu python - <<'PY'
import tempfile
import urllib.request
import numpy as np
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.test_util.chaos import ChaosSpec

tmp = tempfile.mkdtemp(prefix="petastorm_tpu_metrics_smoke_")
schema = Schema("MetricsSmoke", [Field("x", np.int64)])
write_dataset(tmp, schema, [{"x": i} for i in range(60)],
              row_group_size_rows=10)
chaos = ChaosSpec(decode_fail_ordinals=(2,))
with make_batch_reader(tmp, reader_pool_type="thread", workers_count=2,
                       shuffle_row_groups=False, chaos=chaos,
                       on_error="skip", metrics_port=0,
                       sample_interval_s=0.2) as reader:
    port = reader.metrics_server.port
    rows = sorted(x for b in reader.iter_batches() for x in b.columns["x"])
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
assert rows == sorted(set(range(60)) - set(range(20, 30))), len(rows)
assert 'petastorm_tpu_stage_ops_total{stage="decode"}' in body, body[:400]
assert 'petastorm_tpu_stage_latency_seconds{stage="decode"' in body
assert "petastorm_tpu_liveness_hung_workers_killed_total" in body
assert "petastorm_tpu_errors_skipped_rowgroups_total 1" in body
diag = reader.diagnostics
assert diag["telemetry"]["counters"]["errors.skipped_rowgroups"] == 1
print(f"metrics endpoint smoke OK (port {port}, {len(body.splitlines())}"
      " exposition lines, stage_decode + liveness series present,"
      " final snapshot attached)")
PY

echo "== decode smoke (batched multi-core decode + io window under chaos) =="
# a short image read with a hard worker kill must COMPLETE exactly (requeue),
# take the batched native decode path (decode.batch_* series emitted), and
# read each remote rowgroup in ONE ranged read (io.reads_per_rowgroup) -
# the batch-fused decode contract of ISSUE 6.  The native lib was force-built
# in step 1, so a silent cv2 fallback here is a CI failure, not a slow pass.
JAX_PLATFORMS=cpu timeout -k 10 120 python - <<'PY'
import tempfile
import numpy as np
from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.telemetry import Telemetry
from petastorm_tpu.test_util.chaos import ChaosSpec
from petastorm_tpu.test_util.latency_fs import latent_filesystem
from petastorm_tpu.test_util.synthetic import synthetic_rgb_image

tmp = tempfile.mkdtemp(prefix="petastorm_tpu_decode_smoke_")
schema = Schema("DecodeSmoke", [
    Field("label", np.int64, (), ScalarCodec()),
    Field("image", np.uint8, (48, 48, 3), CompressedImageCodec("jpeg", quality=90)),
])
write_dataset(tmp, schema,
              [{"label": i, "image": synthetic_rgb_image(i, 48, 48)}
               for i in range(48)], row_group_size_rows=8)
fs, _ = latent_filesystem(latency_s=0.0)  # remote-shaped fs: window path arms
tele = Telemetry()
chaos = ChaosSpec(kill_ordinals=(2,))
with make_batch_reader(tmp, reader_pool_type="thread", workers_count=2,
                       shuffle_row_groups=False, filesystem=fs, chaos=chaos,
                       telemetry=tele) as reader:
    labels = sorted(int(x) for b in reader.iter_batches()
                    for x in b.columns["label"])
    diag = reader.diagnostics
assert labels == list(range(48)), len(labels)
assert diag["requeued_items"] >= 1, diag
assert diag["native"]["image_decode"], diag["native"]
counters = tele.snapshot()["counters"]
assert counters.get("decode.batch_calls", 0) >= 6, counters
assert counters["decode.batch_images"] >= 48, counters
assert counters["io.rowgroups_read"] >= 6, counters
ratio = counters["io.read_calls"] / counters["io.rowgroups_read"]
assert ratio <= 1.01, f"read amplification {ratio:.2f} reads/rowgroup"
print("decode smoke OK"
      f" ({int(counters['decode.batch_images'])} images via"
      f" {int(counters['decode.batch_calls'])} batched native calls,"
      f" {ratio:.2f} reads/rowgroup, kill requeued, {len(labels)} rows)")
PY

echo "== autotune smoke (closed-loop knob tuning during a chaos read) =="
# a short worker-bound chaos read with autotune armed (fast-paced policy -
# the production pacing is seconds-scale, see docs/operations.md
# "Autotuning") must deliver the exact row multiset, record >= 1 tuning
# decision, and expose the decision trail in diagnostics + autotune.*
# counters - the self-tuning contract of ISSUE 5
JAX_PLATFORMS=cpu timeout -k 10 120 python - <<'PY'
import tempfile
import time
import numpy as np
from petastorm_tpu.autotune import AutotunePolicy
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.telemetry import Telemetry
from petastorm_tpu.test_util.chaos import ChaosSpec
from petastorm_tpu.transform import TransformSpec

tmp = tempfile.mkdtemp(prefix="petastorm_tpu_autotune_smoke_")
schema = Schema("AutotuneSmoke", [Field("x", np.int64)])
write_dataset(tmp, schema, [{"x": i} for i in range(400)],
              row_group_size_rows=4)

def slow(cols):
    time.sleep(0.01)
    return cols

tele = Telemetry()
chaos = ChaosSpec(kill_ordinals=(4,))
policy = AutotunePolicy(warmup_s=0.2, settle_s=0.2, tick_s=0.05,
                        eval_points=2, cooldown_s=0.1)
with make_batch_reader(tmp, reader_pool_type="thread", workers_count=1,
                       shuffle_row_groups=False, num_epochs=2, chaos=chaos,
                       transform_spec=TransformSpec(slow), telemetry=tele,
                       autotune=policy, sample_interval_s=0.1) as reader:
    assert reader.autotune is not None, "autotune did not arm"
    rows = sorted(x for b in reader.iter_batches() for x in b.columns["x"])
    diag = reader.diagnostics
assert rows == sorted(list(range(400)) * 2), len(rows)
at = diag["autotune"]
assert at["moves_applied"] >= 1, at
assert at["decisions"], at
assert diag["requeued_items"] >= 1, diag
counters = tele.snapshot()["counters"]
assert counters["autotune.moves_applied"] == at["moves_applied"]
print("autotune smoke OK"
      f" ({at['moves_applied']} move(s) applied, {at['moves_kept']} kept,"
      f" {at['moves_reverted']} reverted; final knobs {at['knobs']};"
      f" {len(rows)} rows delivered exactly once under a worker kill)")
PY

echo "== warm-cache smoke (shared tier: warm re-read with zero extra decodes) =="
# reader A decodes a jpeg dataset cold into the shared warm tier; reader B -
# a NEW reader over the same tier - must deliver the exact rows with cache
# hits and ZERO additional rowgroup decodes (decode.batch_calls delta == 0):
# the cross-reader warm-tier contract of ISSUE 7
JAX_PLATFORMS=cpu timeout -k 10 120 python - <<'PY'
import tempfile
import numpy as np
from petastorm_tpu.cache_shared import SharedWarmCache
from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.telemetry import Telemetry
from petastorm_tpu.test_util.synthetic import synthetic_rgb_image

tmp = tempfile.mkdtemp(prefix="petastorm_tpu_warm_smoke_")
tier = tempfile.mkdtemp(prefix="petastorm_tpu_warm_tier_")
schema = Schema("WarmSmoke", [
    Field("label", np.int64, (), ScalarCodec()),
    Field("image", np.uint8, (48, 48, 3), CompressedImageCodec("jpeg", quality=90)),
])
write_dataset(tmp, schema,
              [{"label": i, "image": synthetic_rgb_image(i, 48, 48)}
               for i in range(48)], row_group_size_rows=8)

def read(tele):
    with make_batch_reader(tmp, reader_pool_type="thread", workers_count=2,
                           shuffle_row_groups=False, cache_type="shared",
                           cache_location=tier, telemetry=tele) as reader:
        return sorted(int(x) for b in reader.iter_batches()
                      for x in b.columns["label"])

tele_a, tele_b = Telemetry(), Telemetry()
rows_a = read(tele_a)
rows_b = read(tele_b)
assert rows_a == rows_b == list(range(48)), (len(rows_a), len(rows_b))
ca = tele_a.snapshot()["counters"]
cb = tele_b.snapshot()["counters"]
assert ca["cache.misses"] == 6, ca
assert ca.get("decode.batch_calls", 0) >= 6, ca      # cold epoch decoded
assert cb["cache.hits"] >= 6, cb                     # warm re-read hit the tier
assert cb.get("decode.batch_calls", 0) == 0, cb      # with ZERO extra decodes
SharedWarmCache(location=tier).cleanup()
print("warm-cache smoke OK"
      f" (cold: {int(ca['cache.misses'])} misses,"
      f" {int(ca['decode.batch_calls'])} batched decodes; warm re-read:"
      f" {int(cb['cache.hits'])} hits, 0 decodes, rows exact)")
PY

echo "== transform-warm smoke (post-transform caching: warm epoch skips decode AND transform) =="
# a deterministic transform over the shared tier: reader A decodes+transforms
# cold; reader B - a NEW reader, same tier - must deliver the exact
# transformed rows with cache.transform_hits > 0, ZERO additional rowgroup
# decodes AND zero transform stage samples - the ISSUE 15 contract that warm
# epochs skip both stages (docs/operations.md "Transform caching & the
# pipeline planner")
JAX_PLATFORMS=cpu timeout -k 10 120 python - <<'PY'
import tempfile
import numpy as np
from petastorm_tpu.cache_shared import SharedWarmCache
from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.telemetry import Telemetry
from petastorm_tpu.test_util.synthetic import synthetic_rgb_image
from petastorm_tpu.transform import TransformSpec

tmp = tempfile.mkdtemp(prefix="petastorm_tpu_tfwarm_smoke_")
tier = tempfile.mkdtemp(prefix="petastorm_tpu_tfwarm_tier_")
schema = Schema("TfWarmSmoke", [
    Field("label", np.int64, (), ScalarCodec()),
    Field("image", np.uint8, (48, 48, 3), CompressedImageCodec("jpeg", quality=90)),
])
write_dataset(tmp, schema,
              [{"label": i, "image": synthetic_rgb_image(i, 48, 48)}
               for i in range(48)], row_group_size_rows=8)

def brighten(cols):
    out = dict(cols)
    out["label"] = cols["label"] + 1000
    return out

def read(tele):
    spec = TransformSpec(brighten, deterministic=True)
    with make_batch_reader(tmp, reader_pool_type="thread", workers_count=2,
                           shuffle_row_groups=False, cache_type="shared",
                           cache_location=tier, transform_spec=spec,
                           telemetry=tele) as reader:
        return sorted(int(x) for b in reader.iter_batches()
                      for x in b.columns["label"])

tele_a, tele_b = Telemetry(), Telemetry()
rows_a = read(tele_a)
rows_b = read(tele_b)
assert rows_a == rows_b == [i + 1000 for i in range(48)], (rows_a[:3], rows_b[:3])
ca = tele_a.snapshot()["counters"]
cb = tele_b.snapshot()["counters"]
assert ca["cache.transform_stores"] == 6, ca           # cold: 6 rowgroups stored
assert ca["stage.transform.count"] == 6, ca            # transform ran cold only
assert cb["cache.transform_hits"] >= 6, cb             # warm re-read hit the tier
assert cb.get("decode.batch_calls", 0) == 0, cb        # ZERO extra decodes
assert cb.get("stage.transform.count", 0) == 0, cb     # ZERO transform samples
assert cb.get("stage.decode.count", 0) == 0, cb        # ZERO decode samples
SharedWarmCache(location=tier).cleanup()
print("transform-warm smoke OK"
      f" (cold: {int(ca['cache.transform_stores'])} post-transform stores,"
      f" {int(ca['stage.transform.count'])} transform runs; warm re-read:"
      f" {int(cb['cache.transform_hits'])} transform hits, 0 decodes,"
      " 0 transform stage samples, rows exact)")
PY

echo "== planner smoke (cold read writes a flight profile, a second process starts from it) =="
# the ISSUE 15 planner contract across REAL processes: an autotuned cold
# read plans from parquet metadata, converges, and persists a flight
# profile at stop; a SECOND reader process over the same cache location
# must plan >= 1 knob from that profile (provenance 'profile'), deliver the
# exact rows, and surface the verdict in diagnostics['planner']
PLANNER_SMOKE_DIR="$(mktemp -d /tmp/petastorm_tpu_planner_smoke_XXXXXX)"
PLANNER_SMOKE="$(mktemp /tmp/petastorm_tpu_planner_smoke_XXXXXX.py)"
cat > "$PLANNER_SMOKE" <<'PY'
import json
import os
import sys

import numpy as np

from petastorm_tpu.autotune import AutotunePolicy
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema

base, phase = sys.argv[1], sys.argv[2]
url = os.path.join(base, "ds")
loc = os.path.join(base, "profiles")
if phase == "cold":
    schema = Schema("PlannerSmoke", [Field("x", np.int64)])
    write_dataset(url, schema, [{"x": i} for i in range(400)],
                  row_group_size_rows=4)
policy = AutotunePolicy(warmup_s=0.2, settle_s=0.2, tick_s=0.05,
                        eval_points=2, cooldown_s=0.1)
with make_batch_reader(url, reader_pool_type="thread", workers_count="auto",
                       shuffle_row_groups=False, autotune=policy,
                       cache_location=loc, sample_interval_s=0.1) as r:
    assert r.planner is not None, "planner did not run"
    rows = sorted(int(v) for b in r.iter_batches() for v in b.columns["x"])
    diag = r.diagnostics["planner"]
    profile_path = r.planner.profile_path
assert rows == list(range(400)), len(rows)
knobs = diag["knobs"]
if phase == "warm":
    srcs = {k: v["source"] for k, v in knobs.items()}
    assert any(s == "profile" for s in srcs.values()), srcs
    nondefault = [k for k, v in knobs.items()
                  if v["source"] in ("profile", "metadata")]
    assert nondefault, knobs
    print("warm plan sources:", json.dumps(srcs))
print(f"{phase} OK: planned {json.dumps({k: v['value'] for k, v in knobs.items()})}")
PY
JAX_PLATFORMS=cpu timeout -k 10 120 python "$PLANNER_SMOKE" "$PLANNER_SMOKE_DIR" cold
PROFILE_COUNT=$(find "$PLANNER_SMOKE_DIR" -name 'profile-*.json' | wc -l)
[ "$PROFILE_COUNT" -ge 1 ] || {
    echo "planner smoke FAILED: cold run wrote no flight profile"; exit 1; }
JAX_PLATFORMS=cpu timeout -k 10 120 python "$PLANNER_SMOKE" "$PLANNER_SMOKE_DIR" warm
rm -rf "$PLANNER_SMOKE_DIR" "$PLANNER_SMOKE"
echo "planner smoke OK (cold run persisted a flight profile; a second"
echo "  process planned from it with >= 1 non-default knob + exact rows)"

echo "== service smoke (disaggregated ingest: dispatcher + fleet + 2 clients, one worker SIGKILLed) =="
# the full service topology as REAL subprocesses: a dispatcher (CLI), two
# fleet workers (CLI), and two trainer clients, with one worker SIGKILLed
# while it holds in-flight work.  Both clients must deliver their exact row
# multiset and the dispatcher's service.requeued_items must account for the
# kill - the disaggregated-ingest contract of ISSUE 9 (docs/operations.md
# "Disaggregated ingest service").  The dispatcher's wire-mix counters
# (scraped off the stats frame) must show the result data path ran
# PICKLE-FREE: every delivered batch a binary frame, zero pickle fallbacks
# - the ISSUE 12 contract.
SVC_SMOKE="$(mktemp /tmp/petastorm_tpu_service_smoke_XXXXXX.py)"
cat > "$SVC_SMOKE" <<'PY'
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.service.protocol import connect_frames, parse_address

CLIENT = """
import sys
from petastorm_tpu.reader import make_batch_reader
with make_batch_reader(sys.argv[1], service_address=sys.argv[2],
                       shuffle_row_groups=False) as reader:
    rows = sorted(x for b in reader.iter_batches() for x in b.columns["x"])
print("ROWS", len(rows), sum(rows))
"""

def stats(addr):
    conn = connect_frames(parse_address(addr), timeout=5.0)
    try:
        conn.send({"t": "stats?"})
        return conn.recv(timeout=5.0)["stats"]
    finally:
        conn.close()

if __name__ == "__main__":
    tmp = tempfile.mkdtemp(prefix="petastorm_tpu_service_smoke_")
    schema = Schema("ServiceSmoke", [Field("x", np.int64)])
    write_dataset(tmp, schema, [{"x": i} for i in range(400)],
                  row_group_size_rows=10)
    procs = []
    try:
        disp = subprocess.Popen(
            [sys.executable, "-m", "petastorm_tpu.service.cli", "dispatcher",
             "--host", "127.0.0.1", "--port", "0",
             "--heartbeat-timeout", "5"],
            stdout=subprocess.PIPE, text=True)
        procs.append(disp)
        line = disp.stdout.readline()
        addr = re.search(r"listening on (\S+)", line).group(1)
        for i in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "petastorm_tpu.service.cli", "worker",
                 "--address", addr, "--capacity", "2", "--name", f"w{i}"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.monotonic() + 30
        while len(stats(addr)["workers"]) < 2:
            assert time.monotonic() < deadline, "workers never registered"
            time.sleep(0.1)
        clients = [subprocess.Popen([sys.executable, "-c", CLIENT, tmp, addr],
                                    stdout=subprocess.PIPE, text=True)
                   for _ in range(2)]
        procs.extend(clients)
        deadline = time.monotonic() + 30
        while stats(addr)["workers"].get("w0", {}).get("inflight", 0) == 0:
            assert time.monotonic() < deadline, "w0 never took work"
            time.sleep(0.05)
        os.kill(procs[1].pid, signal.SIGKILL)  # w0, mid-epoch
        for client in clients:
            out, _ = client.communicate(timeout=150)
            assert client.returncode == 0, f"client exited {client.returncode}"
            n, total = map(int, out.strip().split()[1:])
            assert (n, total) == (400, sum(range(400))), (n, total)
        s = stats(addr)
        requeued = s["counters"].get("service.requeued_items", 0)
        assert requeued >= 1, s["counters"]
        # the v2 wire contract: the result data path ran pickle-free (2
        # clients x 40 rowgroups = 80 delivered batches, all binary frames)
        binary = s["counters"].get("service.frames_binary", 0)
        fallback = s["counters"].get("service.frames_pickle_fallback", 0)
        assert binary >= 80, s["counters"]
        assert fallback == 0, s["counters"]
        print("service smoke OK (2 clients exact under a worker SIGKILL,"
              f" {int(requeued)} item(s) requeued, {int(binary)} binary"
              f" frames / {int(fallback)} pickle fallbacks, fleet="
              f"{sorted(s['workers'])})")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
PY
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 200 python "$SVC_SMOKE"
rm -f "$SVC_SMOKE"

echo "== trace smoke (distributed tracing: merged cross-process trace + fleet Prometheus) =="
# the ISSUE 19 observability contract, end to end with REAL subprocesses: a
# CLI dispatcher (metrics port armed) + two CLI workers serve one traced
# client (trace_items=1); one worker is SIGKILLed while holding in-flight
# work.  The client's MERGED Chrome trace must contain spans from >= 3
# distinct processes (client + dispatcher + worker tracks), the forced
# requeue must be visible as its own annotated span under the same trace
# id, the hop decomposition must sum (within tolerance) to the observed
# end-to-end latency, and the dispatcher's Prometheus scrape must carry
# per-worker-labeled fleet families (docs/operations.md "Distributed
# tracing & fleet view").
TRACE_SMOKE="$(mktemp /tmp/petastorm_tpu_trace_smoke_XXXXXX.py)"
cat > "$TRACE_SMOKE" <<'PY'
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.service.protocol import connect_frames, parse_address
from petastorm_tpu.telemetry import Telemetry

def stats(addr):
    conn = connect_frames(parse_address(addr), timeout=5.0)
    try:
        conn.send({"t": "stats?"})
        return conn.recv(timeout=5.0)["stats"]
    finally:
        conn.close()

if __name__ == "__main__":
    tmp = tempfile.mkdtemp(prefix="petastorm_tpu_trace_smoke_")
    schema = Schema("TraceSmoke", [Field("x", np.int64)])
    write_dataset(tmp, schema, [{"x": i} for i in range(400)],
                  row_group_size_rows=10)
    procs = []
    try:
        disp = subprocess.Popen(
            [sys.executable, "-m", "petastorm_tpu.service.cli", "dispatcher",
             "--host", "127.0.0.1", "--port", "0", "--metrics-port", "0",
             "--heartbeat-timeout", "5"],
            stdout=subprocess.PIPE, text=True)
        procs.append(disp)
        addr = re.search(r"listening on (\S+)",
                         disp.stdout.readline()).group(1)
        metrics_url = re.search(r"metrics: (\S+)",
                                disp.stdout.readline()).group(1)
        for i in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "petastorm_tpu.service.cli", "worker",
                 "--address", addr, "--capacity", "1", "--name", f"tw{i}"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.monotonic() + 30
        while len(stats(addr)["workers"]) < 2:
            assert time.monotonic() < deadline, "workers never registered"
            time.sleep(0.1)
        # per-worker-labeled fleet families, live before the kill
        scrape = urllib.request.urlopen(metrics_url, timeout=10).read() \
            .decode()
        for w in ("tw0", "tw1"):
            assert f'petastorm_tpu_fleet_worker_up{{worker="{w}"}} 1' \
                in scrape, scrape[:2000]
        tele = Telemetry()
        rows, killed = [], threading.Event()

        def kill_one_mid_item():
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if any(w.get("inflight", 0) > 0
                       for w in stats(addr)["workers"].values()):
                    procs[1].send_signal(signal.SIGKILL)  # tw0 mid-item
                    killed.set()
                    return
                time.sleep(0.05)

        killer = threading.Thread(target=kill_one_mid_item, daemon=True)
        killer.start()
        with make_batch_reader(tmp, service_address=addr,
                               shuffle_row_groups=False, telemetry=tele,
                               trace_items=1) as reader:
            for b in reader.iter_batches():
                rows.extend(b.columns["x"])
        killer.join(timeout=60)
        assert killed.is_set(), "no worker ever held in-flight work"
        assert sorted(rows) == list(range(400)), len(rows)
        trace = tele.trace.chrome_trace()
        spans = [e for e in trace["traceEvents"]
                 if e.get("cat") == "service.trace" and e.get("ph") == "X"]
        pids = {e["pid"] for e in spans}
        assert len(pids) >= 3, f"want client+dispatcher+worker: {pids}"
        requeues = [e for e in spans if e["name"] == "dispatch.requeue"]
        assert requeues, "forced requeue must surface in the merged trace"
        rq_tid = requeues[0]["args"]["trace_id"]
        attempts = {e["args"].get("attempt") for e in spans
                    if e["args"].get("trace_id") == rq_tid
                    and "attempt" in e["args"]}
        assert len(attempts) >= 2, attempts  # both attempts, one trace id
        # hop decomposition telescopes to the end-to-end latency
        hists = tele.snapshot()["histograms"]
        hop = {n[len("service.hop."):]: h["sum"] for n, h in hists.items()
               if n.startswith("service.hop.")}
        parts = ("client_serialize", "dispatcher_queue", "relay",
                 "worker_queue", "worker_exec", "return_relay",
                 "client_deserialize")
        assert set(parts) <= set(hop), sorted(hop)
        decomposed = sum(hop[p] for p in parts)
        assert abs(decomposed - hop["total"]) <= 0.05 * hop["total"], \
            (decomposed, hop["total"])
        requeued = stats(addr)["counters"].get("service.requeued_items", 0)
        assert requeued >= 1
        print("trace smoke OK (merged trace spans"
              f" {len(pids)} processes, requeue visible under one trace"
              f" id, hop decomposition {decomposed:.3f}s ~="
              f" {hop['total']:.3f}s end-to-end,"
              f" {int(requeued)} item(s) requeued,"
              " per-worker Prometheus families labeled)")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
PY
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 200 python "$TRACE_SMOKE"
rm -f "$TRACE_SMOKE"

echo "== dispatcher-kill smoke (SIGKILL the dispatcher mid-epoch, restart, both clients exact) =="
# the ISSUE 13 crash-recovery contract, end to end with REAL subprocesses:
# a CLI dispatcher serving two trainer clients and two rejoin-armed CLI
# workers is SIGKILLed while BOTH clients hold in-flight work, then
# restarted on the same port.  Both clients must finish their epoch with
# the exact row multiset (zero duplicate deliveries - the client ledger +
# resync reconstruct the session on the fresh dispatcher), each client's
# diagnostics must count the restart, and the replacement dispatcher's
# counters must account for the recovery (sessions reconstructed, workers
# rejoined).  docs/operations.md "Fault domains".
KILL_SMOKE="$(mktemp /tmp/petastorm_tpu_kill_smoke_XXXXXX.py)"
cat > "$KILL_SMOKE" <<'PY'
import re
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.service.protocol import connect_frames, parse_address

CLIENT = """
import sys
from petastorm_tpu.reader import make_batch_reader
with make_batch_reader(sys.argv[1], service_address=sys.argv[2],
                       shuffle_row_groups=False) as reader:
    rows = sorted(x for b in reader.iter_batches() for x in b.columns["x"])
    diag = reader.diagnostics
assert rows == list(range(400)), (
    f"row multiset wrong: {len(rows)} rows"  # exact = zero dups, zero losses
)
print("ROWS", len(rows), sum(rows), diag["dispatcher_restarts"])
"""

DISPATCHER = [sys.executable, "-m", "petastorm_tpu.service.cli",
              "dispatcher", "--host", "127.0.0.1",
              "--heartbeat-timeout", "5"]

def stats(addr):
    conn = connect_frames(parse_address(addr), timeout=5.0)
    try:
        conn.send({"t": "stats?"})
        return conn.recv(timeout=5.0)["stats"]
    finally:
        conn.close()

if __name__ == "__main__":
    tmp = tempfile.mkdtemp(prefix="petastorm_tpu_kill_smoke_")
    schema = Schema("KillSmoke", [Field("x", np.int64)])
    write_dataset(tmp, schema, [{"x": i} for i in range(400)],
                  row_group_size_rows=10)
    procs = []
    try:
        disp = subprocess.Popen(DISPATCHER + ["--port", "0"],
                                stdout=subprocess.PIPE, text=True)
        procs.append(disp)
        line = disp.stdout.readline()
        addr = re.search(r"listening on (\S+)", line).group(1)
        port = addr.rsplit(":", 1)[1]
        for i in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "petastorm_tpu.service.cli", "worker",
                 "--address", addr, "--capacity", "2", "--name", f"kw{i}",
                 "--reconnect-attempts", "60"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.monotonic() + 30
        while len(stats(addr)["workers"]) < 2:
            assert time.monotonic() < deadline, "workers never registered"
            time.sleep(0.1)
        clients = [subprocess.Popen([sys.executable, "-c", CLIENT, tmp, addr],
                                    stdout=subprocess.PIPE, text=True)
                   for _ in range(2)]
        procs.extend(clients)
        deadline = time.monotonic() + 30
        while True:
            cs = stats(addr)["clients"]
            if len(cs) == 2 and all(c["inflight"] > 0 for c in cs.values()):
                break  # BOTH clients hold in-flight work at the dispatcher
            assert time.monotonic() < deadline, f"clients never inflight: {cs}"
            time.sleep(0.05)
        disp.send_signal(signal.SIGKILL)  # every session dies with it
        disp.wait(timeout=10)
        time.sleep(0.5)  # a dark window both peers must ride out
        disp2 = subprocess.Popen(DISPATCHER + ["--port", port],
                                 stdout=subprocess.PIPE, text=True)
        procs.append(disp2)
        assert "listening" in disp2.stdout.readline()
        for client in clients:
            out, _ = client.communicate(timeout=150)
            assert client.returncode == 0, f"client exited {client.returncode}"
            n, total, restarts = map(int, out.strip().split()[1:])
            assert (n, total) == (400, sum(range(400))), (n, total)
            assert restarts == 1, f"client saw {restarts} restarts"
        s = stats(addr)
        c = s["counters"]
        assert c.get("service.sessions_reconstructed", 0) >= 2, c
        assert c.get("service.worker_rejoins", 0) >= 2, c
        print("dispatcher-kill smoke OK (2 clients exact through a"
              " dispatcher SIGKILL+restart;"
              f" {int(c['service.sessions_reconstructed'])} sessions"
              f" reconstructed, {int(c['service.worker_rejoins'])} worker"
              f" rejoins, {int(c.get('service.recovered_assignments', 0))}"
              " assignments re-attached,"
              f" {int(c.get('service.resync_items_restored', 0))} items"
              " restored by resync)")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
PY
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 200 python "$KILL_SMOKE"
rm -f "$KILL_SMOKE"

echo "== failover smoke (SIGKILL the primary mid-epoch, hot standby promotes, both clients exact) =="
# the ISSUE 17 hot-standby HA contract, end to end with REAL subprocesses:
# a journaled CLI primary feeds a CLI standby over journal_sync; two
# rejoin-armed workers and two trainer clients dial the failover address
# list.  With BOTH clients holding in-flight work and the standby at lag
# 0 (asserted BEFORE the kill), the primary is SIGKILLed: the standby
# must promote and serve its first assignment within 5s, both clients
# must finish with the exact row multiset (zero duplicate deliveries off
# the warm mirror), and the promoted standby must count exactly one
# failover with a bumped epoch.  docs/operations.md "Dispatcher HA".
HA_SMOKE="$(mktemp /tmp/petastorm_tpu_ha_smoke_XXXXXX.py)"
cat > "$HA_SMOKE" <<'PY'
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.service.protocol import connect_frames, parse_address

CLIENT = """
import sys
from petastorm_tpu.reader import make_batch_reader
with make_batch_reader(sys.argv[1], service_address=sys.argv[2],
                       shuffle_row_groups=False) as reader:
    rows = sorted(x for b in reader.iter_batches() for x in b.columns["x"])
    diag = reader.diagnostics
assert rows == list(range(400)), (
    f"row multiset wrong: {len(rows)} rows"  # exact = zero dups, zero losses
)
print("ROWS", len(rows), sum(rows), diag["dispatcher_restarts"])
"""

CLI = [sys.executable, "-m", "petastorm_tpu.service.cli"]

def stats(addr):
    conn = connect_frames(parse_address(addr), timeout=5.0)
    try:
        conn.send({"t": "stats?"})
        return conn.recv(timeout=5.0)["stats"]
    finally:
        conn.close()

if __name__ == "__main__":
    tmp = tempfile.mkdtemp(prefix="petastorm_tpu_ha_smoke_")
    schema = Schema("HASmoke", [Field("x", np.int64)])
    write_dataset(tmp, schema, [{"x": i} for i in range(400)],
                  row_group_size_rows=10)
    journal = tmp + ".journal"  # SIBLING of the dataset dir, not inside it
    procs = []
    try:
        primary = subprocess.Popen(
            CLI + ["dispatcher", "--host", "127.0.0.1", "--port", "0",
                   "--heartbeat-timeout", "5", "--journal", journal,
                   "--journal-fsync"],
            stdout=subprocess.PIPE, text=True)
        procs.append(primary)
        p_addr = re.search(r"listening on (\S+)",
                           primary.stdout.readline()).group(1)
        standby = subprocess.Popen(
            CLI + ["dispatcher", "--host", "127.0.0.1", "--port", "0",
                   "--heartbeat-timeout", "5", "--standby-of", p_addr],
            stdout=subprocess.PIPE, text=True)
        procs.append(standby)
        s_addr = re.search(r"listening on (\S+)",
                           standby.stdout.readline()).group(1)
        peers = f"{p_addr},{s_addr}"  # the failover address list
        for i in range(2):
            procs.append(subprocess.Popen(
                CLI + ["worker", "--address", peers, "--capacity", "2",
                       "--name", f"haw{i}", "--reconnect-attempts", "240"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.monotonic() + 30
        while len(stats(p_addr)["workers"]) < 2:
            assert time.monotonic() < deadline, "workers never registered"
            time.sleep(0.1)
        clients = [subprocess.Popen(
            [sys.executable, "-c", CLIENT, tmp, peers],
            stdout=subprocess.PIPE, text=True) for _ in range(2)]
        procs.extend(clients)
        deadline = time.monotonic() + 30
        while True:
            cs = stats(p_addr)["clients"]
            if len(cs) == 2 and all(c["inflight"] > 0 for c in cs.values()):
                break  # BOTH clients hold in-flight work at the primary
            assert time.monotonic() < deadline, f"clients never inflight: {cs}"
            time.sleep(0.05)
        # the standby must be WARM before the kill: synced, zero lag
        deadline = time.monotonic() + 30
        while True:
            sb = stats(s_addr)["standby"]
            if sb["synced_records"] > 0 and sb["lag_items"] == 0:
                break
            assert time.monotonic() < deadline, f"standby never warm: {sb}"
            time.sleep(0.05)
        assert not sb["promoted"], sb
        primary.send_signal(signal.SIGKILL)  # every session dies with it
        killed_at = time.monotonic()
        primary.wait(timeout=10)
        # heartbeat-time failover: the promoted standby serves its first
        # assignment (a client holds in-flight work on IT) within 5s
        while True:
            s = stats(s_addr)
            if s["standby"]["promoted"] and any(
                    c["inflight"] > 0 for c in s["clients"].values()):
                break
            assert time.monotonic() - killed_at < 5.0, (
                f"standby did not serve within 5s of the kill: {s['standby']}")
            time.sleep(0.05)
        first_serve_s = time.monotonic() - killed_at
        for client in clients:
            out, _ = client.communicate(timeout=150)
            assert client.returncode == 0, f"client exited {client.returncode}"
            n, total, restarts = map(int, out.strip().split()[1:])
            assert (n, total) == (400, sum(range(400))), (n, total)
            assert restarts >= 1, f"client never rolled over: {restarts}"
        s = stats(s_addr)
        c = s["counters"]
        assert c.get("service.failovers", 0) == 1, c
        assert s["epoch"] >= 2, s["epoch"]
        assert c.get("service.worker_rejoins", 0) >= 2, c
        print("failover smoke OK (2 clients exact through a primary"
              f" SIGKILL; standby served {first_serve_s:.2f}s after the"
              f" kill at epoch {int(s['epoch'])},"
              f" {int(c.get('service.journal_items_restored', 0))} warm"
              " item(s) restored,"
              f" {int(c['service.worker_rejoins'])} worker rejoins)")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
PY
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 200 python "$HA_SMOKE"
rm -f "$HA_SMOKE"

echo "== autoscale smoke (closed loop: starved client forces a scale-up, idle drain a graceful retire) =="
# the full ISSUE 14 loop as real CLI processes under timeout: a dispatcher,
# a `petastorm-tpu-service autoscale` supervisor (floor 1 / ceiling 2), and
# one starved trainer.  The supervisor must spawn the second worker off
# sustained pressure DURING the read, the idle fleet afterwards must shrink
# via a GRACEFUL retire (drain, flush, bye - no force-kill), the client's
# row multiset must be exact through the scale events, and the
# service.autoscale.workers_spawned/retired counters must prove both moves.
AUTOSCALE_SMOKE="$(mktemp /tmp/petastorm_tpu_autoscale_smoke_XXXXXX.py)"
cat > "$AUTOSCALE_SMOKE" <<'PY'
import collections
import json
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.service.protocol import connect_frames, parse_address
from petastorm_tpu.test_util.synthetic import synthetic_rgb_image

tmp = tempfile.mkdtemp(prefix="petastorm_tpu_autoscale_smoke_")
url = f"{tmp}/img"
# starvation is piggybacked on ~1s client_stats frames and the loop wants
# 2 consecutive pressured polls: the read must span several seconds on the
# 1-worker fleet for the scale-up to fire mid-read
n_rows, epochs = 96, 30
schema = Schema("Img", [
    Field("label", np.int64, (), ScalarCodec()),
    Field("image", np.uint8, (224, 224, 3),
          CompressedImageCodec("jpeg", quality=90)),
])
write_dataset(url, schema,
              [{"label": i, "image": synthetic_rgb_image(i, 224, 224)}
               for i in range(n_rows)], row_group_size_rows=16)

def stats(addr):
    conn = connect_frames(parse_address(addr), timeout=5.0)
    try:
        conn.send({"t": "stats?"})
        return conn.recv(timeout=5.0)["stats"]
    finally:
        conn.close()

events = []
procs = []
try:
    disp = subprocess.Popen(
        [sys.executable, "-m", "petastorm_tpu.service.cli", "dispatcher",
         "--host", "127.0.0.1", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    procs.append(disp)
    addr = re.search(r"listening on (\S+)",
                     disp.stdout.readline()).group(1)
    sup = subprocess.Popen(
        [sys.executable, "-m", "petastorm_tpu.service.cli", "autoscale",
         "--address", addr, "--min-workers", "1", "--max-workers", "2",
         "--capacity", "1", "--poll-interval", "0.25",
         "--grow-windows", "2", "--shrink-windows", "6",
         "--settle", "0.5", "--starved-threshold", "0.02",
         "--drain-timeout", "20"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    procs.append(sup)

    def pump():
        for line in sup.stdout:
            try:
                events.append(json.loads(line))
            except ValueError:
                pass

    pumper = threading.Thread(target=pump, daemon=True)
    pumper.start()

    def wait_for(cond, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.1)
        raise AssertionError(f"timed out waiting for {what}: {events}")

    # the min_workers floor brings worker #1 up without any client
    wait_for(lambda: len(stats(addr)["workers"]) >= 1, 30, "floor worker")

    # one greedy trainer: the 1-worker fleet starves it -> the loop must
    # spawn worker #2 DURING the read (sustained pressure, 2 polls)
    got = []
    with make_batch_reader(url, shuffle_row_groups=False,
                           num_epochs=epochs, service_address=addr) as r:
        for b in r.iter_batches():
            got.extend(int(v) for v in b.columns["label"])
    assert collections.Counter(got) == collections.Counter(
        list(range(n_rows)) * epochs), "row multiset not exact"
    grow_events = [e for e in events if e.get("event") == "scale-up"
                   and "pressure" in e.get("reason", "")]
    assert grow_events, f"no pressure-driven scale-up fired: {events}"
    assert len(stats(addr)["workers"]) == 2, stats(addr)["workers"]

    # the read is done, the client gone: the idle fleet must shrink back
    # to the floor via a GRACEFUL retire (scale_pressure decays out of its
    # 10s window first, then 6 shrink verdicts accumulate)
    wait_for(lambda: any(e.get("event") == "scale-down" for e in events),
             45, "graceful scale-down")
    down = [e for e in events if e.get("event") == "scale-down"]
    assert all(e.get("graceful") for e in down), down
    wait_for(lambda: len(stats(addr)["workers"]) == 1, 30, "fleet at floor")
    dc = stats(addr)["counters"]
    assert dc.get("service.qos.workers_draining", 0) >= 1, dc
    assert dc.get("service.requeued_items", 0) == 0, dc  # drained, not moved

    # SIGTERM = drain the spawned fleet and exit with a counters summary
    sup.send_signal(signal.SIGTERM)
    sup.wait(timeout=60)
    pumper.join(timeout=5)
    summary = [e for e in events if e.get("event") == "stopped"][-1]["summary"]
    c = summary["counters"]
    assert c["workers_spawned"] >= 2, c   # floor + pressure-driven grow
    assert c["workers_retired"] >= 2, c   # idle shrink + shutdown drain
    assert c["workers_force_killed"] == 0, c
    assert c["scale_ups"] >= 2, c         # floor bring-up counts as one
    assert c["scale_downs"] >= 1, c
    print("autoscale smoke OK (floor up, pressure scale-up mid-read, exact"
          f" rows, graceful idle shrink + shutdown drain;"
          f" spawned={int(c['workers_spawned'])}"
          f" retired={int(c['workers_retired'])} force_killed=0)")
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
PY
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 300 python "$AUTOSCALE_SMOKE"
rm -f "$AUTOSCALE_SMOKE"

echo "== determinism smoke (seed-stable delivery: identical stream digests across configs) =="
# two SUBPROCESS runs of petastorm-tpu-diagnose over ONE dataset - different
# worker counts, the second with a chaos worker kill - must print identical
# stream_digest lines; a third run with a different seed must differ.  The
# smoke and operators share one code path: --stream-digest
# (docs/operations.md "Reproducibility").
DET_DS="$(mktemp -d /tmp/petastorm_tpu_det_smoke_XXXXXX)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$DET_DS" <<'PY'
import sys
import numpy as np
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.schema import Field, Schema
schema = Schema("DetSmoke", [Field("x", np.int64)])
write_dataset(sys.argv[1], schema, [{"x": i} for i in range(300)],
              row_group_size_rows=10)
PY
DET_A="$(JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 120 \
    python -m petastorm_tpu.tools.diagnose "$DET_DS" --seed 7 \
    --stream-digest -w 2 --num-epochs 2 | grep '^stream_digest')"
DET_B="$(JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 120 \
    python -m petastorm_tpu.tools.diagnose "$DET_DS" --seed 7 \
    --stream-digest -w 4 --num-epochs 2 --chaos 'kill_ordinals=3' \
    | grep '^stream_digest')"
DET_C="$(JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 120 \
    python -m petastorm_tpu.tools.diagnose "$DET_DS" --seed 8 \
    --stream-digest -w 2 --num-epochs 2 | grep '^stream_digest')"
rm -rf "$DET_DS"
echo "  run A (2w):          $DET_A"
echo "  run B (4w + kill):   $DET_B"
echo "  run C (other seed):  $DET_C"
[ -n "$DET_A" ] || { echo "determinism smoke FAILED: no digest line"; exit 1; }
[ "$DET_A" = "$DET_B" ] || {
    echo "determinism smoke FAILED: digests differ across configs"; exit 1; }
[ "$DET_A" != "$DET_C" ] || {
    echo "determinism smoke FAILED: different seeds produced equal digests"
    exit 1; }
echo "determinism smoke OK (2w == 4w+kill, seed 7 != seed 8)"

echo "== sequence smoke (token pipeline: packed 2-corpus mixture digest stable across configs) =="
# two SUBPROCESS runs over one 2-corpus token mixture - different worker
# counts, the second with a chaos worker kill - must print identical
# packed-stream + mixture digests; a third run with a different seed must
# differ.  Packing fill-rate must clear the ISSUE 11 floor (>= 0.85).
SEQ_DS="$(mktemp -d /tmp/petastorm_tpu_seq_smoke_XXXXXX)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$SEQ_DS" <<'PY'
import sys
from petastorm_tpu.test_util.synthetic import write_token_corpus
for i in range(2):
    write_token_corpus(f"{sys.argv[1]}/c{i}", n_docs=120, rows_per_rg=10,
                       mean_len=24, max_len=100, seed=90 + i)
PY
SEQ_SMOKE="$(mktemp /tmp/petastorm_tpu_seq_smoke_XXXXXX.py)"
cat > "$SEQ_SMOKE" <<'PY'
import sys

from petastorm_tpu.test_util.matrix import MatrixCell, run_sequence_cell

base, workers, chaos, seed = sys.argv[1:5]
urls = [f"{base}/c0", f"{base}/c1"]
cell = MatrixCell(workers=int(workers), pool="thread", chaos=chaos)
r = run_sequence_cell(urls, int(seed), cell, num_epochs=2)
assert r.fill_rate >= 0.85, f"fill-rate {r.fill_rate} below the 0.85 floor"
print(f"packed_digest {r.packed_crc:08x}"
      f" mixture={r.mixture['combined']} tokens={r.tokens}")
PY
SEQ_A="$(JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 120 \
    python "$SEQ_SMOKE" "$SEQ_DS" 2 none 7 | grep '^packed_digest')"
SEQ_B="$(JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 120 \
    python "$SEQ_SMOKE" "$SEQ_DS" 4 kill 7 2>/dev/null | grep '^packed_digest')"
SEQ_C="$(JAX_PLATFORMS=cpu PYTHONPATH="$PWD" timeout -k 10 120 \
    python "$SEQ_SMOKE" "$SEQ_DS" 2 none 8 | grep '^packed_digest')"
rm -rf "$SEQ_DS" "$SEQ_SMOKE"
echo "  run A (2w):          $SEQ_A"
echo "  run B (4w + kill):   $SEQ_B"
echo "  run C (other seed):  $SEQ_C"
[ -n "$SEQ_A" ] || { echo "sequence smoke FAILED: no digest line"; exit 1; }
[ "$SEQ_A" = "$SEQ_B" ] || {
    echo "sequence smoke FAILED: packed digests differ across configs"
    exit 1; }
[ "$SEQ_A" != "$SEQ_C" ] || {
    echo "sequence smoke FAILED: different seeds produced equal packed digests"
    exit 1; }
echo "sequence smoke OK (2w == 4w+kill, seed 7 != seed 8, fill >= 0.85)"

echo "== driver entry compile-check =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python __graft_entry__.py 8
echo "CI OK"
