"""Throughput benchmark - one JSON line per BASELINE.json config.

The driver parses the LAST line, so the headline metric (the reference's only
published number: hello_world read rate, 709.84 samples/sec from
/root/reference/docs/benchmarks_tutorial.rst:20-21, measured via
/root/reference/petastorm/benchmark/throughput.py:113-174 defaults - thread
pool x3, 200 warmup / 1000 measured rows) prints last.  The four other
BASELINE.json configs print first, each with ``vs_baseline`` relative to the
round-2 recorded value (the reference publishes no number for them), so
regressions are visible round over round.

Configs (BASELINE.md):
  1. mnist-style Parquet via make_reader (single-process CPU row path)
  2. hello_world Unischema (PNG + variable 4-D ndarray)  <- headline, LAST
  3. imagenet CompressedImageCodec(jpeg) -> device feed (JaxDataLoader,
     on-chip hybrid decode when the chip is present)
  4. converter: in-memory data -> cached parquet -> jax loader
  5. NGram timestamped multi-frame window readout
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# glibc keeps multi-MB batch buffers pooled instead of returning them to the
# kernel per free (docs/operations.md); must be set before numpy allocates,
# so re-exec once with the env in place
if os.environ.get("_PST_BENCH_CHILD") != "1":
    # TF_CPP_MIN_LOG_LEVEL/GRPC_VERBOSITY: TF/absl/oneDNN/grpc banners on
    # stderr truncated the driver's BENCH_r03 tail capture; silence them
    # HERE so every child inherits the quiet env too
    env = dict(os.environ, _PST_BENCH_CHILD="1",
               MALLOC_MMAP_THRESHOLD_="268435456",
               MALLOC_TRIM_THRESHOLD_="268435456",
               TF_CPP_MIN_LOG_LEVEL="3",
               GRPC_VERBOSITY="ERROR")
    os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)]
              + sys.argv[1:], env)

sys.setswitchinterval(0.001)

BASELINE_SAMPLES_PER_SEC = 709.84  # reference hello_world (BASELINE.md)
#: round-2 recorded values - regression reference for configs the
#: reference publishes no number for.  This box's absolute rates drift +-30%
#: between sessions; treat vs_baseline here as
#: a round-over-round regression tripwire, not a precision comparison.  Each
#: drifting config's NOTE also carries a same-session anchor (raw-pyarrow
#: ceiling fraction / host-decode ratio / shared-core-model agreement) that
#: IS drift-immune - compare those across rounds for the real signal.
R2 = {"mnist_rows_per_sec": 430_000.0,
      "imagenet_ingest_samples_per_sec": 2900.0,
      "converter_rows_per_sec": 305_000.0,
      "ngram_windows_per_sec": 164_000.0}

def _force_device_completion(batch):
    """End-of-segment device sync: fetch ONE element of a device array,
    which waits for all queued work that produced it."""
    import jax

    for v in (batch.values() if hasattr(batch, "values") else [batch]):
        if isinstance(v, jax.Array):
            jax.device_get(v.ravel()[0])
            return


def _raw_ceiling_rows_per_sec(url, repeats: int = 3) -> float:
    """Same-session anchor: raw pyarrow table reads of
    the SAME dataset - the host+pyarrow ceiling with zero framework code.
    Each drifting CPU metric's note reports its rate as a fraction of this
    ceiling, a figure immune to the +-30% host drift (a normalized rate
    that moves round-over-round is code, not drift).  NOT used to rescale
    vs_baseline: no single calibration workload drifts identically to every
    config (verified: mnist ran 1.37x its round-2 rate in the round-4
    session while ingest ran 0.81x), so a shared multiplier would just swap
    one distortion for another."""
    import pyarrow.dataset as pads

    t0 = time.perf_counter()
    for _ in range(repeats):
        n = pads.dataset(url, format="parquet").to_table().num_rows
    return repeats * n / (time.perf_counter() - t0)


def _ceiling_note(rate: float, url) -> str:
    ceiling = _raw_ceiling_rows_per_sec(url)
    return (f"; same-session raw-pyarrow ceiling {ceiling:.0f} rows/s on the"
            f" SAME data - this config at {100 * rate / ceiling:.1f}% of it"
            " (the drift-immune anchor to compare across rounds)")


def _median(rates):
    # median, not max: max is optimistically biased and weakens the
    # round-over-round regression tripwire on a host with +-30% drift
    rates = sorted(rates)
    return rates[len(rates) // 2]


#: every line emitted this run, replayed as one penultimate 'bench_summary'
#: line right before the headline - so ANY tail window of the driver's
#: capture contains every metric even if early lines scroll out
_EMITTED = []

def _emit(metric, value, unit, baseline, note=None):
    line = {"metric": metric, "value": round(value, 2), "unit": unit,
            "vs_baseline": round(value / baseline, 3)}
    if note:
        line["note"] = note
    print(json.dumps(line), flush=True)
    _EMITTED.append(line)
    return line


# -- config 1: mnist row path -------------------------------------------------

def bench_mnist(tmp):
    import numpy as np

    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_reader
    from petastorm_tpu.schema import Field, Schema

    url = os.path.join(tmp, "mnist")
    schema = Schema("Mnist", [
        Field("idx", np.int64, (), ScalarCodec()),
        Field("digit", np.int64, (), ScalarCodec()),
        Field("image", np.uint8, (28, 28), NdarrayCodec()),
    ])
    rng = np.random.default_rng(7)
    rows = [{"idx": i, "digit": i % 10,
             "image": rng.integers(0, 255, (28, 28), dtype=np.uint8)}
            for i in range(4096)]
    write_dataset(url, schema, rows, row_group_size_rows=1024)

    with make_reader(url, reader_pool_type="serial", num_epochs=None,
                     shuffle_row_groups=False) as r:
        it = iter(r)
        for _ in range(4096):  # warm epoch
            next(it)
        t0 = time.perf_counter()
        n = 4 * 4096
        for _ in range(n):
            next(it)
        rate = n / (time.perf_counter() - t0)
    return _emit("mnist_rows_per_sec", rate, "rows/sec",
                 R2["mnist_rows_per_sec"],
                 note="vs round-2 recorded value" + _ceiling_note(rate, url))


# -- remote IO under injected latency ---------------------

def bench_remote_latency(tmp):
    """Same-session A/B: a wide parquet dataset read through a per-call
    20 ms latency-injecting filesystem (test_util.latency_fs - the object
    store cost model) vs the zero-latency wrap of the same local files.
    pre_buffer coalescing + 4 workers must HIDE the latency: the ratio is
    the price of remoteness, and reads/rowgroup quantifies coalescing."""
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.test_util.latency_fs import latent_filesystem
    from petastorm_tpu.test_util.synthetic import write_wide_dataset

    url = os.path.join(tmp, "latent_wide")
    n_cols, n_rg, rows_per_rg = 8, 16, 64
    if not os.path.exists(url):
        write_wide_dataset(url, n_cols=n_cols, n_rowgroups=n_rg,
                           rows_per_rg=rows_per_rg, vec_len=32, seed=3)

    def read_wall(latency):
        fs, stats = latent_filesystem(latency_s=latency)
        t0 = time.perf_counter()
        with make_batch_reader(url, filesystem=fs, shuffle_row_groups=False,
                               num_epochs=1, reader_pool_type="thread",
                               workers_count=4) as r:
            n = sum(cb.num_rows for cb in r.iter_batches())
        assert n == n_rg * rows_per_rg
        return time.perf_counter() - t0, stats.snapshot()

    read_wall(0.0)  # warm the page cache so the A/B measures the wrapper
    # interleaved local/latent pairs, median-of-3: same drift hygiene as
    # the other configs on this +-30% box (see bench_ngram)
    locals_, latents = [], []
    for _ in range(3):
        locals_.append(read_wall(0.0)[0])
        wall, latent_stats = read_wall(0.02)
        latents.append(wall)
    local_wall, latent_wall = _median(locals_), _median(latents)
    ratio = latent_wall / max(local_wall, 1e-6)
    return _emit(
        "remote_ingest_latent_vs_local_ratio", ratio, "x", 1.0,
        note=f"20ms/call injected: {latent_wall:.2f}s vs local"
             f" {local_wall:.2f}s (same session, same files);"
             f" {latent_stats['slept_s']:.1f}s total sleep injected across"
             f" {latent_stats['reads']} reads ="
             f" {latent_stats['reads'] / n_rg:.1f} reads/rowgroup for"
             f" {n_cols} columns (pre_buffer coalescing), hidden by 4"
             " workers; serial payment would add"
             f" {latent_stats['slept_s']:.1f}s to wall")


# -- config 2: hello_world (headline) ----------------------------------------

def bench_hello_world(tmp):
    import numpy as np

    from petastorm_tpu.codecs import CompressedImageCodec, NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_reader
    from petastorm_tpu.schema import Field, Schema

    url = os.path.join(tmp, "hello_world")
    schema = Schema("HelloWorld", [
        Field("id", np.int32, (), ScalarCodec()),
        Field("image1", np.uint8, (128, 256, 3), CompressedImageCodec("png")),
        Field("array_4d", np.uint8, (None, 128, 30, None), NdarrayCodec()),
    ])
    rng = np.random.default_rng(1234)
    rows = [{"id": i,
             "image1": rng.integers(0, 255, (128, 256, 3), dtype=np.uint8),
             "array_4d": rng.integers(0, 255, (4, 128, 30, 3), dtype=np.uint8)}
            for i in range(10)]
    write_dataset(url, schema, rows, row_group_size_mb=256)

    WARMUP, MEASURE, CYCLES = 200, 1000, 5
    with make_reader(url, reader_pool_type="thread", workers_count=3,
                     num_epochs=None) as reader:
        it = iter(reader)
        for _ in range(WARMUP):
            next(it)
        rates = []
        for _ in range(CYCLES):
            t0 = time.perf_counter()
            for _ in range(MEASURE):
                next(it)
            rates.append(MEASURE / (time.perf_counter() - t0))
    # document the environment variance IN the captured line: this box's
    # CPU drifts +-30% between sessions, so the cycle
    # spread distinguishes a drifting host from a code regression
    spread = f"cycle spread {min(rates):.0f}-{max(rates):.0f}"
    return _emit("hello_world_samples_per_sec", _median(rates),
                 "samples/sec", BASELINE_SAMPLES_PER_SEC,
                 note=f"median of {CYCLES}x{MEASURE}-row cycles, {spread}"
                      " samples/sec; r2 capture 3283.71, host drifts +-30%"
                      " between sessions")


# -- config 3: imagenet jpeg -> device feed -----------------------------------

def _ensure_imagenet(tmp):
    """Write the shared 224px jpeg dataset once; several configs read it."""
    import numpy as np

    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.test_util.synthetic import synthetic_rgb_image

    url = os.path.join(tmp, "imagenet224")
    if os.path.exists(url):
        return url
    schema = Schema("Img", [
        Field("label", np.int64, (), ScalarCodec()),
        Field("image", np.uint8, (224, 224, 3),
              CompressedImageCodec("jpeg", quality=90)),
    ])
    rows = [{"label": i % 1000, "image": synthetic_rgb_image(i, 224, 224)}
            for i in range(256)]
    write_dataset(url, schema, rows, row_group_size_rows=32)
    return url


def _multicore_decode_baseline(url):
    """The HONEST decoder baseline (PAPERS.md: single-thread JPEG decoder
    benchmarks mis-evaluate ML data loaders): a thread pool across every
    usable core running cv2.imdecode + BGR->RGB over the SAME stored jpeg
    bytes this config ingests - no framework, no IO (bytes pre-loaded), no
    transfer.  Any loader number must be judged against THIS ceiling, not a
    one-core decode loop; it is also a same-session anchor immune to host
    drift."""
    import concurrent.futures as cf

    import cv2
    import numpy as np
    import pyarrow.dataset as pads

    bufs = [c.as_py() for c in
            pads.dataset(url, format="parquet").to_table(
                columns=["image"]).column("image").combine_chunks()]
    threads = os.cpu_count() or 1

    def decode(buf):
        img = cv2.imdecode(np.frombuffer(buf, np.uint8), cv2.IMREAD_COLOR)
        return cv2.cvtColor(img, cv2.COLOR_BGR2RGB)

    with cf.ThreadPoolExecutor(threads) as pool:
        list(pool.map(decode, bufs))  # warmup (thread spawn, cv2 init)
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            list(pool.map(decode, bufs))
            rates.append(len(bufs) / (time.perf_counter() - t0))
    return _median(rates), threads


def bench_imagenet(tmp):
    _require_device_runtime()
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.reader import make_batch_reader

    url = _ensure_imagenet(tmp)

    import jax

    from petastorm_tpu.native import image as native_image
    placement = ({"image": "device"} if native_image.available()
                 and jax.default_backend() != "cpu" else None)

    baseline_rate, baseline_threads = _multicore_decode_baseline(url)
    _emit("imagenet_decode_multicore_baseline_samples_per_sec", baseline_rate,
          "samples/sec", R2["imagenet_ingest_samples_per_sec"],
          note=f"thread-pooled cv2 decode of the SAME jpeg bytes across"
               f" {baseline_threads} cores, no IO/framework/transfer - the"
               " honest decode ceiling the ingest number is judged against"
               " (replaces the single-threaded strawman; PAPERS.md)")

    # steady-state measurement: warm the pipeline (jit compile, file cache,
    # queue fill), then time a fixed batch count mid-stream.  decode_threads
    # defaults to 'auto', so the single-worker reader decodes multi-core
    # (the pipeline must be as multi-core as the baseline to compare fairly)
    with make_batch_reader(url, num_epochs=None, workers_count=1,
                           shuffle_row_groups=False,
                           decode_placement=placement) as r:
        with JaxDataLoader(r, batch_size=32, prefetch=3) as loader:
            it = iter(loader)
            for _ in range(16):
                b = next(it)
            _force_device_completion(b)   # warmup fully landed
            rates = []
            for _ in range(3):
                n = 0
                t0 = time.perf_counter()
                for _ in range(32):
                    b = next(it)
                    n += int(b["image"].shape[0])
                # ONE sync per segment: per-batch syncs would time the
                # syncs, not the feed
                _force_device_completion(b)
                rates.append(n / (time.perf_counter() - t0))
    rate = _median(rates)
    return _emit("imagenet_ingest_samples_per_sec", rate, "samples/sec",
                 R2["imagenet_ingest_samples_per_sec"],
                 note=f"decode={'hybrid-device' if placement else 'host'};"
                      " median-of-3 vs round-2 recorded max-of-3;"
                      f" {100 * rate / baseline_rate:.0f}% of the"
                      f" same-session {baseline_threads}-core decode"
                      f" baseline ({baseline_rate:.0f}/s, drift-immune)"
                      + _ceiling_note(rate, url))


def bench_imagenet_mixed(tmp):
    """device-mixed on the REAL chip: a 2-geometry jpeg
    dataset through the canvas finish, with the host decode of the SAME
    mixed data, measured just before, in the note (and the uniform-device
    number from bench_imagenet for cross-reference).  Round 4 proved mixed
    decode works; this shows whether the canvas padding gives the hybrid
    win back."""
    _require_device_runtime()
    import numpy as np

    import jax

    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.native import image as native_image
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.test_util.synthetic import synthetic_rgb_image

    geoms = ((224, 224), (192, 256))
    target = (224, 256, 3)
    url = os.path.join(tmp, "imagenet224mix")
    if not os.path.exists(url):
        schema = Schema("ImgMix", [
            Field("label", np.int64, (), ScalarCodec()),
            Field("image", np.uint8, (None, None, 3),
                  CompressedImageCodec("jpeg", quality=90)),
        ])
        rows = [{"label": i % 1000,
                 "image": synthetic_rgb_image(i, *geoms[i % len(geoms)])}
                for i in range(256)]
        write_dataset(url, schema, rows, row_group_size_rows=32)

    def run(placement):
        with make_batch_reader(url, num_epochs=None, workers_count=1,
                               shuffle_row_groups=False,
                               decode_placement=placement) as r:
            with JaxDataLoader(r, batch_size=32, prefetch=3,
                               pad_shapes={"image": target}) as loader:
                it = iter(loader)
                for _ in range(16):
                    b = next(it)
                _force_device_completion(b)
                rates = []
                for _ in range(3):
                    n = 0
                    t0 = time.perf_counter()
                    for _ in range(24):
                        b = next(it)
                        n += int(b["image"].shape[0])
                    _force_device_completion(b)
                    rates.append(n / (time.perf_counter() - t0))
        return _median(rates)

    on_chip = native_image.available() and jax.default_backend() != "cpu"
    host_rate = run(None)
    if not on_chip:
        return _emit("imagenet_ingest_mixed_samples_per_sec", host_rate,
                     "samples/sec", R2["imagenet_ingest_samples_per_sec"],
                     note="HOST decode only (no chip/native lib); 2-geometry"
                          f" jpeg dataset {geoms}, pad target {target}")
    mixed_rate = run({"image": "device-mixed"})
    uniform = next((ln["value"] for ln in _EMITTED
                    if ln["metric"] == "imagenet_ingest_samples_per_sec"),
                   None)
    # same-session anchor: the host decode of the SAME mixed data measured
    # seconds ago - vs_baseline is the device-vs-host speedup, immune to
    # host drift
    return _emit(
        "imagenet_ingest_mixed_samples_per_sec", mixed_rate, "samples/sec",
        max(host_rate, 1e-6),
        note=f"2-geometry jpeg dataset {geoms} via device-mixed"
             f" (canvas finish), canvas {target}; vs_baseline ="
             " ratio to the same-session HOST decode of the SAME mixed data"
             f" ({host_rate:.0f} samples/s - the drift-immune anchor);"
             f" uniform-geometry device decode this session:"
             f" {uniform if uniform is not None else 'n/a'}")


# -- north star: same jpeg dataset through ours vs best-effort tf.data --------

def bench_north_star(tmp):
    """BASELINE.json's north star is >=90% of tf.data.service samples/sec/chip;
    tf.data-local (TFRecord -> decode_jpeg -> batch -> prefetch(AUTOTUNE)) is
    the honest proxy measurable on this box.  Both pipelines read the SAME
    jpeg-compressed images, deliver uint8 batches to the SAME jax device, and
    run the SAME jitted normalize-reduce consumer; trials are interleaved
    A/B/A/B so host drift hits both equally.
    Harness contract: reference petastorm/benchmark/throughput.py:113-174.
    """
    _require_device_runtime()
    import numpy as np

    url = _ensure_imagenet(tmp)

    import jax
    import jax.numpy as jnp

    import logging as _logging

    _logging.getLogger("absl").setLevel(_logging.ERROR)
    # TF's C++ bootstrap writes I0000 oneDNN/cuda banners straight to fd 2
    # BEFORE absl log init, ignoring TF_CPP_MIN_LOG_LEVEL - exactly the noise
    # that truncated the driver's BENCH_r03 tail capture.  Silence fd 2 for
    # the import only (python-level stderr/exceptions are unaffected after).
    devnull = os.open(os.devnull, os.O_WRONLY)
    saved_fd2 = os.dup(2)
    os.dup2(devnull, 2)
    try:
        import tensorflow as tf  # noqa: PLC0415 - heavyweight, scoped here
    finally:
        os.dup2(saved_fd2, 2)
        os.close(saved_fd2)
        os.close(devnull)

    tf.get_logger().setLevel("ERROR")

    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.native import image as native_image
    from petastorm_tpu.reader import make_batch_reader

    # extract the STORED jpeg bytes so tf.data reads its native format
    # (TFRecord) with zero parquet overhead - best effort for tf.data
    import pyarrow.dataset as pads

    table = pads.dataset(url, format="parquet").to_table(
        columns=["label", "image"])
    jpegs = table.column("image").to_pylist()
    labels = table.column("label").to_pylist()
    tfr = os.path.join(tmp, "north_star.tfrecord")
    if not os.path.exists(tfr):
        with tf.io.TFRecordWriter(tfr) as w:
            for b, lbl in zip(jpegs, labels):
                ex = tf.train.Example(features=tf.train.Features(feature={
                    "image": tf.train.Feature(
                        bytes_list=tf.train.BytesList(value=[b])),
                    "label": tf.train.Feature(
                        int64_list=tf.train.Int64List(value=[int(lbl)]))}))
                w.write(ex.SerializeToString())

    BATCH, BATCHES, WARM = 32, 32, 8
    consume = jax.jit(lambda x: ((x.astype(jnp.float32) / 255.0) - 0.5).sum())

    placement = ({"image": "device"} if native_image.available()
                 and jax.default_backend() != "cpu" else None)

    def run_ours():
        with make_batch_reader(url, num_epochs=None, workers_count=1,
                               shuffle_row_groups=False,
                               decode_placement=placement) as r:
            with JaxDataLoader(r, batch_size=BATCH, prefetch=3) as loader:
                it = iter(loader)
                for _ in range(WARM):
                    jax.block_until_ready(consume(next(it)["image"]))
                t0 = time.perf_counter()
                for _ in range(BATCHES):
                    jax.block_until_ready(consume(next(it)["image"]))
                return BATCH * BATCHES / (time.perf_counter() - t0)

    feat = {"image": tf.io.FixedLenFeature([], tf.string),
            "label": tf.io.FixedLenFeature([], tf.int64)}

    def _parse(raw):
        ex = tf.io.parse_single_example(raw, feat)
        return tf.io.decode_jpeg(ex["image"], channels=3), ex["label"]

    def run_tfdata():
        ds = (tf.data.TFRecordDataset(tfr).repeat()
                .map(_parse, num_parallel_calls=tf.data.AUTOTUNE,
                     deterministic=False)
                .batch(BATCH).prefetch(tf.data.AUTOTUNE))
        it = ds.as_numpy_iterator()
        for _ in range(WARM):
            img, lbl = next(it)
            jax.block_until_ready(consume(jax.device_put(img)))
        t0 = time.perf_counter()
        for _ in range(BATCHES):
            img, lbl = next(it)
            b = jax.device_put({"image": img, "label": lbl})
            jax.block_until_ready(consume(b["image"]))
        return BATCH * BATCHES / (time.perf_counter() - t0)

    ours, tfd = [], []
    for _ in range(3):  # interleaved: drift hits both pipelines equally
        ours.append(run_ours())
        tfd.append(run_tfdata())
    ratio = _median(ours) / _median(tfd)
    return _emit("north_star_vs_tfdata_ratio", ratio, "x", 0.9,
                 note=f"ours={_median(ours):.0f} tf.data={_median(tfd):.0f}"
                      f" samples/sec, interleaved median-of-3,"
                      f" decode={'hybrid-device' if placement else 'host'};"
                      " vs_baseline>=1.0 meets the >=0.9x-of-tf.data target")


# -- north star under REAL training: tf.data vs ours, same train loop ---------

def _child_env():
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)  # APPEND to PYTHONPATH: the jax plugin site must stay
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env


_BACKEND_CACHE: dict = {}


def _backend_in_child(env):
    """Probe the default backend in a CHILD so the parent process never
    initializes the device runtime (train subprocesses must own the chip
    exclusively: a chip belongs to one process).  A hung runtime (first device
    op never returns) yields 'unreachable' instead of hanging the whole bench -
    device configs then SKIP while the host-only configs (incl. the hello_world
    headline) still emit."""
    import subprocess

    key = env.get("JAX_PLATFORMS", "")
    if key in _BACKEND_CACHE:
        return _BACKEND_CACHE[key]
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; x = jax.numpy.ones((2, 2));"
             " float((x @ x).sum()); print(jax.default_backend())"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=env, timeout=300)
        result = probe.stdout.strip().splitlines()[-1] if probe.stdout.strip() else ""
    except subprocess.TimeoutExpired:
        result = "unreachable"
    _BACKEND_CACHE[key] = result
    return result


def _require_device_runtime():
    """Raise (-> a recorded per-config error, not a hang) when the device
    runtime cannot complete one op; the caller would otherwise initialize
    jax IN-PROCESS and hang the entire bench on a dead runtime."""
    if _backend_in_child(_child_env()) == "unreachable":
        raise RuntimeError(
            "device runtime unreachable (probe op never returned);"
            " skipping this device-touching config")


def bench_north_star_train(tmp):
    """The north star measured under REAL training: tf.data vs this loader
    feeding the SAME ResNet-50 train loop (same stored jpegs, same jitted
    train_step, symmetric background device transfer - examples/imagenet/
    train_resnet_tpu.py --input).  Fresh-process interleaved A/B/A/B so
    host drift hits both pipelines equally; reports samples/sec/chip
    AND the input-attributable device-idle%% for both.  Retires the r3 gap
    that the 1.51x ingest-only ratio was measured with a trivial jitted
    reduce, not train steps (BASELINE.json north_star is a training metric).
    """
    import subprocess

    env = _child_env()
    backend = _backend_in_child(env)
    if backend == "unreachable":
        raise RuntimeError("device runtime unreachable (probe op never"
                           " returned); skipping - train children would hang"
                           " against a dead runtime")
    on_chip = backend not in ("cpu", "")
    if on_chip:
        url = _ensure_imagenet(tmp)
        shape = ["--steps", "200", "--global-batch", "32", "--side", "224"]
    else:
        url = os.path.join(tmp, "imagenet64")
        from examples.imagenet.train_resnet_tpu import generate_dataset

        if not os.path.exists(url):
            generate_dataset(url, rows=64, side=64)
        shape = ["--steps", "4", "--global-batch", "8", "--side", "64",
                 "--num-classes", "10"]
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "examples", "imagenet", "train_resnet_tpu.py")

    def run(input_):
        out = subprocess.run(
            [sys.executable, script, "--dataset-url", url, "--skip-generate",
             "--workers", "1", "--prefetch", "3", "--decode", "device",
             "--cache", "null", "--input", input_, "--json"] + shape,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, timeout=900, check=True)
        # stderr captured (not forwarded): it would pollute the driver's
        # tail capture
        return json.loads(out.stdout.strip().splitlines()[-1])

    ours, tfd = [], []
    t0 = time.perf_counter()
    pairs = 1
    ours.append(run("petastorm"))
    tfd.append(run("tfdata"))
    # each run pays process start + jit compile (minutes on a slow day);
    # spend a second interleaved pair only when the budget allows, so the
    # whole bench cannot outgrow the driver's capture window
    if time.perf_counter() - t0 < 480:
        ours.append(run("petastorm"))
        tfd.append(run("tfdata"))
        pairs = 2

    def mean(ms, key):
        return sum(m[key] for m in ms) / len(ms)

    om, tm = (mean(ours, "samples_per_sec_per_chip"),
              mean(tfd, "samples_per_sec_per_chip"))
    oi, ti = mean(ours, "device_idle_pct"), mean(tfd, "device_idle_pct")
    return _emit("north_star_train_ratio", om / tm, "x", 0.9,
                 note=f"REAL ResNet-50 train steps ({ours[0]['steps']}/run,"
                      f" fresh-process interleaved A/B x{pairs}, cold cache):"
                      f" ours {om:.0f} samples/s/chip @ {oi:.1f}% input idle"
                      f" vs tf.data {tm:.0f} @ {ti:.1f}%;"
                      " vs_baseline>=1.0 meets the >=0.9x-of-tf.data target")


# -- real-training input stall: ResNet-50 train steps -------------------------

def bench_train_stall(tmp):
    """200 REAL ResNet-50 train steps fed by the loader: samples/sec/chip
    plus the device-idle%% attributable to input (consumer wait / wall).
    On a CPU-only backend (no chip) the shape shrinks so the config stays
    runnable; the driver's capture on the real chip is the number of record.
    """
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    env = _child_env()
    # this config runs FIRST so the parent has not initialized the device
    # runtime and the train subprocesses own the chip exclusively
    backend = _backend_in_child(env)
    if backend == "unreachable":
        raise RuntimeError("device runtime unreachable (probe op never"
                           " returned); skipping - train children would hang"
                           " against a dead runtime")
    on_chip = backend not in ("cpu", "")
    if on_chip:
        url = _ensure_imagenet(tmp)
        shape = ["--steps", "200", "--global-batch", "32", "--side", "224"]
    else:
        url = os.path.join(tmp, "imagenet64")
        from examples.imagenet.train_resnet_tpu import generate_dataset

        if not os.path.exists(url):
            generate_dataset(url, rows=64, side=64)
        shape = ["--steps", "4", "--global-batch", "8", "--side", "64",
                 "--num-classes", "10"]

    script = os.path.join(repo, "examples", "imagenet", "train_resnet_tpu.py")

    def run(cache, scan=1):
        # each measurement in a FRESH process: the device runtime's dispatch
        # path degrades unpredictably under sustained in-process load on this
        # host, which poisons back-to-back
        # in-process measurements
        out = subprocess.run(
            [sys.executable, script, "--dataset-url", url, "--skip-generate",
             "--workers", "1", "--prefetch", "3", "--decode", "device",
             "--cache", cache, "--scan-steps", str(scan), "--json"] + shape,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, timeout=900, check=True)
        # stderr captured (not forwarded): it would pollute the driver's
        # tail capture
        return json.loads(out.stdout.strip().splitlines()[-1])

    # nominal dense bf16 peaks by device kind - the FALLBACK denominator
    # when the example's same-session matmul probe gives none
    peak_flops = {"TPU v5 lite": 197e12, "TPU v5e": 197e12,
                  "TPU v4": 275e12, "TPU v3": 123e12, "TPU v2": 45e12}

    def peak_for(m):
        measured = m.get("measured_peak_flops")
        if measured:
            return measured, "same-session matmul probe"
        kind = m.get("device_kind", "")
        return peak_flops.get(kind), f"nominal {kind} table value"

    def mfu_pct(m, flops_from=None):
        """Model-FLOPs utilization: XLA's own cost-analysis FLOPs for the
        compiled train dispatch (fwd+bwd+optimizer), per sample, times the
        measured samples/s/chip, over the chip's MEASURED peak (same FMA=2
        convention on both sides).  ``flops_from`` supplies the per-sample
        FLOPs for scan-mode runs (XLA counts a lax.scan body once, so the
        scan executable's figure is unusable; the scan=1 run of the same
        model/shapes is the right source)."""
        src = flops_from or m
        f = src.get("flops_per_sample")
        peak, _ = peak_for(m)
        if not f or not peak:
            return None
        return 100.0 * m["samples_per_sec_per_chip"] * f / peak

    cold = run("null")
    # warm host LRU: epochs after the first skip parquet+entropy-decode -
    # the steady state for any dataset that fits host RAM
    warm = run("memory")
    _emit("imagenet_train_device_idle_pct", cold["device_idle_pct"], "%",
          100.0,  # vs_baseline here = idle fraction of wall time (lower=better)
          note=f"input-attributable idle over {cold['steps']} real ResNet"
               f" train steps, decode={cold['decode']}, cold cache;"
               f" warm memory cache: {warm['device_idle_pct']:.1f}%."
               " This host has ONE cpu core feeding the chip; a v5e host"
               " has ~14 cores/chip")
    _emit("imagenet_train_warm_cache_samples_per_sec_per_chip",
          warm["samples_per_sec_per_chip"], "samples/sec/chip", 1230.0,
          note=f"{warm['steps']} real train steps, global_batch="
               f"{warm['global_batch']}, decode={warm['decode']},"
               " warm memory LRU; vs round-1 recorded 1230")
    warm_mfu = mfu_pct(warm)
    if warm_mfu is not None:
        peak, peak_src = peak_for(warm)
        _emit("imagenet_train_mfu_pct", warm_mfu, "%", 100.0,
              note=f"scan=1 warm: {warm['samples_per_sec_per_chip']:.0f}"
                   f" samples/s/chip x {warm['flops_per_sample']:.3g}"
                   " FLOP/sample (XLA cost_analysis of the compiled"
                   " fwd+bwd+optimizer dispatch) over"
                   f" {peak:.3g} peak FLOP/s ({peak_src};"
                   f" device_kind {warm.get('device_kind')!r}, nominal"
                   f" {peak_flops.get(warm.get('device_kind', ''), 0):.3g});"
                   " vs_baseline = fraction of chip peak (host-independent)")
    line = _emit("imagenet_train_samples_per_sec_per_chip",
                 cold["samples_per_sec_per_chip"], "samples/sec/chip",
                 1230.0,  # round 1 recorded 1230-1340 on this chip
                 note=f"{cold['steps']} real train steps, global_batch="
                      f"{cold['global_batch']}, decode={cold['decode']},"
                      " cold cache; vs round-1 recorded 1230")
    # warm + lax.scan multi-step LAST, after the cold/warm metrics are safely
    # emitted (a failure here must not discard two completed measurements):
    # 8 train steps per dispatch amortizes the fixed per-call dispatch cost
    # - the warm path's bottleneck once ingest is cached
    scan8 = run("memory", scan=8)
    _emit("imagenet_train_warm_scan8_samples_per_sec_per_chip",
          scan8["samples_per_sec_per_chip"], "samples/sec/chip", 1230.0,
          note=f"{scan8['steps']} real train steps, 8 steps/dispatch via"
               " lax.scan fed by JaxDataLoader(stack_batches=8) - one"
               " (8, B, ...) transfer per dispatch; warm memory LRU;"
               " vs round-1 recorded 1230")
    scan8_mfu = mfu_pct(scan8, flops_from=warm)
    if scan8_mfu is not None:
        peak, peak_src = peak_for(scan8)
        _emit("imagenet_train_warm_scan8_mfu_pct", scan8_mfu, "%", 100.0,
              note=f"scan=8 warm: {scan8['samples_per_sec_per_chip']:.0f}"
                   f" samples/s/chip x {warm['flops_per_sample']:.3g}"
                   " FLOP/sample (XLA cost_analysis of the scan=1 compiled"
                   " step - the scan body is identical math) over"
                   f" {peak:.3g} peak FLOP/s ({peak_src});"
                   " vs_baseline = fraction of chip peak")
    if "input_stall_pct" in scan8:
        _emit("imagenet_train_scan8_input_stall_pct",
              scan8["input_stall_pct"], "%", 100.0,
              note="scan-valid stall: measured wall minus a same-session"
                   " compute floor (identical dispatch count on ONE resident"
                   " stacked unit, no input pipeline in the loop), as % of"
                   " wall - valid where consumer_wait is not (scan overlaps"
                   f" it with device work). scan=1 warm comparison:"
                   f" {warm.get('input_stall_pct', float('nan')):.1f}%")
    return line


# -- cold-epoch input floor: why cold idle is what it is ----------------------

def bench_cold_floor(tmp):
    """Quantifies the cold-epoch input stall: the ONE cpu
    core is time-sliced between the train loop's host work and the ingest
    pipeline, so the shared-core model  1/cold = 1/warm + 1/ingest  should
    predict the measured cold train rate from (a) the warm-cache train rate
    (ingest skipped - the non-ingest share of the core) and (b) the
    ingest-only capacity measured here: parquet column read + BATCHED jpeg
    entropy decode (native pack_coef_columns, the exact host work under
    decode='device'; one call per column, so coefficient-read batching is by
    construction the measured path - and with one core, the library's
    nthreads>1 fan-out has nothing to fan onto).  Agreement means the cold
    rate IS the 1-core floor: the mitigation is host cores (a real v5e host
    has ~14 per chip), not code.  Decode-ahead cannot help - it schedules
    the same core it would steal from.
    """
    import pyarrow.dataset as pads

    from petastorm_tpu.native import image as native_image

    if not native_image.available():
        raise RuntimeError("native image library unavailable")
    url = _ensure_imagenet(tmp)

    def read_once():
        return pads.dataset(url, format="parquet").to_table(
            columns=["label", "image"])

    t0 = time.perf_counter()
    for _ in range(3):
        table = read_once()
    n = table.num_rows
    read_rate = 3 * n / (time.perf_counter() - t0)
    col = table.column("image").combine_chunks()
    t0 = time.perf_counter()
    for _ in range(5):
        native_image.pack_coef_columns("image", col)
    entropy_rate = 5 * n / (time.perf_counter() - t0)
    ingest = 1.0 / (1.0 / read_rate + 1.0 / entropy_rate)

    prior = {ln["metric"]: ln["value"] for ln in _EMITTED}
    cold = prior.get("imagenet_train_samples_per_sec_per_chip")
    warm = prior.get("imagenet_train_warm_cache_samples_per_sec_per_chip")
    note = (f"1-core ingest capacity: parquet read {read_rate:.0f} +"
            f" batched entropy decode {entropy_rate:.0f} samples/s"
            " (serial harmonic)")
    # the model note only holds when the train rates came from the SAME
    # 224px dataset measured here - on a cpu backend bench_train_stall used
    # the tiny 64px fallback, an incomparable workload
    if _backend_in_child(_child_env()) in ("cpu", "", "unreachable"):
        cold = warm = None
    if cold and warm:
        pred = 1.0 / (1.0 / warm + 1.0 / ingest)
        note += (f"; shared-core model 1/cold=1/warm+1/ingest predicts"
                 f" {pred:.0f} vs measured cold {cold:.0f} samples/s/chip"
                 f" ({100 * cold / pred:.0f}% of prediction) - cold is the"
                 " 1-core floor, mitigated by host cores (~14/chip on v5e),"
                 " not by code."
                 " vs_baseline = measured/predicted, the SAME-SESSION model"
                 " anchor (the round-4 absolute constant 4287 is retired -"
                 " it drifted with the host, r4 capture hit 0.593 of it in"
                 f" one session); ingest capacity this session: {ingest:.0f}")
        # same-session anchor: how well the model holds, not how fast the
        # host happened to be
        return _emit("cold_input_floor_samples_per_sec", ingest,
                     "samples/sec", ingest * pred / cold, note=note)
    note += ("; no same-session train rates on this backend - vs_baseline"
             " pinned to 1.0 (model anchor unavailable, absolute recorded"
             " for reference only)")
    return _emit("cold_input_floor_samples_per_sec", ingest, "samples/sec",
                 ingest, note=note)


# -- config 4: converter ------------------------------------------------------

def bench_converter(tmp):
    _require_device_runtime()
    import numpy as np
    import pyarrow as pa

    import jax

    from petastorm_tpu.converter import make_converter

    rng = np.random.default_rng(3)
    n, width = 65536, 64
    table = pa.table({f"f{j}": rng.standard_normal(n).astype(np.float32)
                      for j in range(width)})
    conv = make_converter(table, cache_dir_url=os.path.join(tmp, "conv"))
    try:
        with conv.make_jax_loader(
                batch_size=4096, prefetch=3,
                reader_kwargs={"num_epochs": None, "workers_count": 1,
                               "shuffle_row_groups": False}) as loader:
            it = iter(loader)
            for _ in range(24):
                b = next(it)
            _force_device_completion(b)
            rates = []
            for _ in range(3):
                rows = 0
                t0 = time.perf_counter()
                for _ in range(32):
                    b = next(it)
                    rows += int(next(iter(b.values())).shape[0])
                _force_device_completion(b)
                rates.append(rows / (time.perf_counter() - t0))
        rate = _median(rates)
        # anchor on the EXACT materialized dataset the loader read, not the
        # cache parent (debris/second materializations would inflate it)
        suffix = _ceiling_note(rate, conv.cache_url)
    finally:
        conv.delete()
    return _emit("converter_rows_per_sec", rate, "rows/sec",
                 R2["converter_rows_per_sec"],
                 note="median-of-3 vs round-2 recorded max-of-3" + suffix)


# -- autotune convergence: cold bad knobs vs same-session hand-tuned ----------

def bench_autotune(tmp):
    """Closed-loop autotune A/B on the simulated-step stall shape (ISSUE 5
    acceptance): starting from deliberately bad knobs (workers=1, a
    1-deep results queue), an autotuned run must converge toward the
    same-session hand-tuned optimum (>= 80% of it), and turning autotune ON
    over the already-hand-tuned knobs must never cost more than 10% (the
    no-regression guard).  Interleaved rounds, median-of-3, same-session
    hand-tuned anchor.  Host-only
    (reader + thread pool plane; the prefetch knob is exercised by the
    loader tests, not here - this config must run chip or no chip)."""
    import numpy as np

    from petastorm_tpu.autotune import AutotunePolicy
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.schema import Field, Schema

    url = os.path.join(tmp, "autotune_png")
    if not os.path.exists(url):
        rng = np.random.default_rng(11)
        schema = Schema("Tune", [
            Field("label", np.int64, (), ScalarCodec()),
            Field("image", np.uint8, (96, 96, 3), CompressedImageCodec("png")),
        ])
        rows = [{"label": i,
                 "image": rng.integers(0, 255, (96, 96, 3), dtype=np.uint8)}
                for i in range(256)]
        write_dataset(url, schema, rows, row_group_size_rows=8)

    STEP_S = 0.004   # simulated per-batch consumer step (the stall shape)
    DURATION_S = 6.0
    # fast-converging policy: the proof is that the LOOP finds the optimum,
    # not that the production pacing (seconds-scale settle) would in 6s
    policy = AutotunePolicy(warmup_s=0.4, settle_s=0.4, tick_s=0.05,
                            eval_points=2, cooldown_s=0.3, max_workers=8)

    def run(workers, results_queue, autotune):
        rows = 0
        with make_batch_reader(
                url, reader_pool_type="thread", workers_count=workers,
                results_queue_size=results_queue, num_epochs=None,
                shuffle_row_groups=False,
                autotune=policy if autotune else False,
                sample_interval_s=0.2 if autotune else None) as r:
            t0 = time.perf_counter()
            for b in r.iter_batches():
                rows += b.num_rows
                time.sleep(STEP_S)
                if time.perf_counter() - t0 >= DURATION_S:
                    break
            wall = time.perf_counter() - t0
        return rows / wall

    # hand-tuned = this box's recorded optimum shape (worker
    # count peaks at 2 on the 1-core host), default results bound
    bad_auto, hand_off, hand_auto = [], [], []
    for _ in range(3):  # interleaved so host drift hits all three equally
        hand_off.append(run(2, 10, autotune=False))
        bad_auto.append(run(1, 1, autotune=True))
        hand_auto.append(run(2, 10, autotune=True))
    anchor = max(_median(hand_off), 1e-6)
    _emit("autotune_cold_vs_handtuned_ratio", _median(bad_auto) / anchor,
          "x", 0.8,
          note="cold bad knobs (workers=1, results_queue=1) + autotune vs"
               f" same-session hand-tuned (workers=2) over {DURATION_S:.0f}s"
               f" with a {1e3 * STEP_S:.0f}ms simulated step, interleaved"
               f" median-of-3; hand-tuned anchor {anchor:.0f} rows/s;"
               " vs_baseline>=1.0 meets the >=80%-of-hand-tuned target"
               " (convergence time included in the window)")
    return _emit("autotune_on_vs_off_ratio", _median(hand_auto) / anchor,
                 "x", 0.9,
                 note="autotune ON over already-hand-tuned knobs vs the"
                      " identical autotune-OFF run (same session,"
                      " interleaved); vs_baseline>=1.0 meets the >=90%"
                      " no-regression guard")


# -- warm-cache tier: epoch-2 and cross-reader A/B (ISSUE 7) ------------------

def bench_warm_cache(tmp):
    """Shared warm-cache tier A/B on the imagenet_ingest shape (ISSUE 7
    acceptance): epoch 2 of a ``cache_type='shared'`` read must run >= 3x
    epoch 1 (decode+IO skipped: every rowgroup is a shared-memory hit), and
    a SECOND reader running concurrently over the same tier must record
    cross-reader cache hits during its FIRST epoch.  Host-only (the tier is
    entirely host-plane) and same-session anchored: the ratio is
    drift-immune by construction - cold and warm share one process, one
    host, one minute."""
    import threading as _threading

    from petastorm_tpu.cache_shared import SharedWarmCache
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.telemetry import Telemetry

    url = _ensure_imagenet(tmp)
    n_rows = 256  # _ensure_imagenet writes 256 rows in 8 rowgroups

    def one_round(idx):
        """(cold_rate, warm_rate) from epoch 1 vs epoch 2 of one reader on a
        FRESH tier (a reused tier would make epoch 1 warm too)."""
        loc = os.path.join(tmp, f"warm_tier_{idx}")
        try:
            with make_batch_reader(url, reader_pool_type="thread",
                                   workers_count=1,  # ingest shape: 1 worker,
                                   shuffle_row_groups=False,  # multicore decode
                                   cache_type="shared", cache_location=loc,
                                   num_epochs=2) as r:
                rows = 0
                t0 = time.perf_counter()
                t1 = None
                for b in r.iter_batches():
                    rows += b.num_rows
                    if t1 is None and rows >= n_rows:
                        t1 = time.perf_counter()  # epoch boundary
                t2 = time.perf_counter()
            return n_rows / (t1 - t0), n_rows / (t2 - t1)
        finally:
            SharedWarmCache(location=loc).cleanup()

    rounds = [one_round(i) for i in range(3)]
    cold = _median([c for c, _ in rounds])
    warm = _median([w for _, w in rounds])
    ratio = warm / cold
    _emit("warm_cache_warm_epoch_samples_per_sec", warm, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note=f"epoch 2 over the shared tier (every rowgroup a shm hit);"
               f" cold epoch 1 same session: {cold:.0f}/s")
    _emit("warm_cache_epoch2_vs_epoch1_ratio", ratio, "x", 3.0,
          note="median-of-3 interleaved fresh-tier rounds; vs_baseline>=1.0"
               " meets the ISSUE 7 >=3x warm-epoch target (same-session"
               " anchored: drift-immune)")

    # -- two concurrent readers, one tier: cross-reader hits ------------------
    loc = os.path.join(tmp, "warm_tier_xr")
    tele_b = Telemetry()
    try:
        def read_a():
            with make_batch_reader(url, reader_pool_type="thread",
                                   workers_count=1, shuffle_row_groups=False,
                                   cache_type="shared", cache_location=loc,
                                   num_epochs=2) as ra:
                for _ in ra.iter_batches():
                    pass

        a = _threading.Thread(target=read_a)
        a.start()
        time.sleep(0.2)  # let A warm part of the tier
        with make_batch_reader(url, reader_pool_type="thread",
                               workers_count=1, shuffle_row_groups=False,
                               cache_type="shared", cache_location=loc,
                               num_epochs=1, telemetry=tele_b) as rb:
            b_rows = sum(b.num_rows for b in rb.iter_batches())
        a.join()
        counters = tele_b.snapshot()["counters"]
        hits = counters.get("cache.hits", 0) + counters.get("cache.l2_hits", 0)
        items = hits + counters.get("cache.misses", 0)
        assert b_rows == n_rows, b_rows
    finally:
        SharedWarmCache(location=loc).cleanup()
    return _emit("warm_cache_cross_reader_hit_rate",
                 hits / max(items, 1), "fraction", 1.0,
                 note=f"reader B's FIRST epoch over a tier reader A was"
                      f" concurrently warming: {hits:.0f}/{items:.0f} items"
                      " served from the shared tier (ISSUE 7 acceptance:"
                      " > 0 from B's first epoch)")


# -- transform-output caching + planner cold start (ISSUE 15) -----------------

def _bench_heavy_transform(cols):
    """Deliberately transform-dominated work: three float passes over the
    decoded pixels (normalize, signed sqrt, re-quantize).  Pure function of
    its input - the shape post-transform caching exists for."""
    import numpy as np

    img = cols["image"].astype(np.float32)
    img -= img.mean(axis=(1, 2), keepdims=True)
    img = np.sign(img) * np.sqrt(np.abs(img))
    out = dict(cols)
    out["image"] = np.clip(img * 16.0 + 128.0, 0, 255).astype(np.uint8)
    return out


def bench_transform_cache(tmp):
    """Post-transform warm caching A/B on a transform-dominated pipeline
    (ISSUE 15 acceptance): with a deterministic transform, epoch 2 over the
    shared tier must skip decode AND transform (target: beat the decode-only
    13.5x of BENCH_r07 - the transform is the dominant stage here, so
    decode-only caching alone cannot deliver it); the same pipeline with the
    transform declared non-deterministic (decode cached, transform re-runs)
    prices what output caching adds.  All ratios SAME-SESSION anchored
    (drift-immune); floors armed in tools/bench_compare.py."""
    from petastorm_tpu.cache_shared import SharedWarmCache
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.transform import TransformSpec

    url = _ensure_imagenet(tmp)
    n_rows = 256

    def one_round(idx, deterministic):
        """(cold epoch rate, warm epoch rate) on a FRESH tier."""
        loc = os.path.join(tmp, f"tfc_tier_{idx}_{deterministic}")
        spec = TransformSpec(_bench_heavy_transform,
                             deterministic=deterministic)
        try:
            with make_batch_reader(url, reader_pool_type="thread",
                                   workers_count=1, shuffle_row_groups=False,
                                   cache_type="shared", cache_location=loc,
                                   transform_spec=spec, num_epochs=2) as r:
                rows = 0
                t0 = time.perf_counter()
                t1 = None
                for b in r.iter_batches():
                    rows += b.num_rows
                    if t1 is None and rows >= n_rows:
                        t1 = time.perf_counter()  # epoch boundary
                t2 = time.perf_counter()
                stats = (r.warm_cache.stats()
                         if r.warm_cache is not None else {})
            if deterministic:
                assert stats.get("transform_hits", 0) > 0, stats
            else:
                assert stats.get("transform_hits", 0) == 0, stats
            return n_rows / (t1 - t0), n_rows / (t2 - t1)
        finally:
            SharedWarmCache(location=loc).cleanup()

    # interleaved A/B rounds: host drift hits both arms equally
    tf_rounds, dec_rounds = [], []
    for i in range(3):
        tf_rounds.append(one_round(i, True))
        dec_rounds.append(one_round(i, False))
    cold = _median([c for c, _ in tf_rounds])
    warm = _median([w for _, w in tf_rounds])
    warm_decode_only = _median([w for _, w in dec_rounds])
    _emit("transform_warm_vs_cold_ratio", warm / cold, "x", 13.5,
          note="warm epoch over cold epoch with a transform-dominated"
               " pipeline and post-transform caching armed (median-of-3"
               " fresh-tier rounds, same-session anchored); the baseline is"
               " BENCH_r07's decode-only 13.5x warm ratio - vs_baseline"
               " >= 1.0 means transform skipping beats it; absolute floor"
               " 3.0 (bench_compare)")
    return _emit(
        "transform_warm_vs_decode_only_warm_ratio",
        warm / max(warm_decode_only, 1e-9), "x", 1.0,
        note="the SAME warm epoch with the transform declared"
             " non-deterministic re-runs the transform per rowgroup"
             f" ({warm_decode_only:.0f} rows/s vs {warm:.0f} rows/s with"
             " output caching) - this ratio is post-transform caching's"
             " own win on top of decode caching; absolute floor 1.2")


def bench_planner_cold_start(tmp):
    """Planner cold-start A/B (ISSUE 15 acceptance): time-to-90%-of-peak
    throughput for a reader seeded by a recorded flight profile vs the old
    explore-from-static-defaults runtime climb.  The workload is the object
    -store cost model (test_util.latency_fs, 30ms per read call): hiding
    per-read latency needs a WIDE worker plane regardless of core count, so
    the static single-host seed starts deep in the bad region and the
    autotune loop must climb workers one judged move at a time - while the
    flight profile jumps straight to the converged width.  t90 is measured
    against a SHARED target (90% of the planner-seeded arm's steady rate,
    per interleaved pair), clipped to the run window when never reached.
    Ratio = explore t90 / planned t90, same-session anchored; absolute
    floor 1.2 armed in tools/bench_compare.py."""
    from petastorm_tpu.autotune import AutotunePolicy
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.test_util.latency_fs import latent_filesystem
    from petastorm_tpu.test_util.synthetic import write_wide_dataset

    url = os.path.join(tmp, "planner_latent")
    n_rg, rows_per_rg = 24, 64
    if not os.path.exists(url):
        write_wide_dataset(url, n_cols=8, n_rowgroups=n_rg,
                           rows_per_rg=rows_per_rg, vec_len=32, seed=13)

    LATENCY_S = 0.03
    DURATION_S = 6.0
    W = 8  # sliding-window batches for the instantaneous rate

    def policy(planner):
        return AutotunePolicy(warmup_s=0.4, settle_s=0.4, tick_s=0.05,
                              eval_points=2, cooldown_s=0.3, max_workers=8,
                              planner=planner)

    def run(loc, duration=DURATION_S, **kwargs):
        """[(t, cumulative rows)] per consumed batch over ``duration``."""
        fs, _stats = latent_filesystem(latency_s=LATENCY_S)
        points = []
        with make_batch_reader(url, reader_pool_type="thread",
                               filesystem=fs, num_epochs=None,
                               shuffle_row_groups=False, cache_location=loc,
                               sample_interval_s=0.2, **kwargs) as r:
            rows = 0
            t0 = time.perf_counter()
            for b in r.iter_batches():
                rows += b.num_rows
                points.append((time.perf_counter() - t0, rows))
                if points[-1][0] >= duration:
                    break
        return points

    def steady(points):
        """Delivered rate over the run's second half."""
        half = next(i for i, (t, _) in enumerate(points)
                    if t >= points[-1][0] / 2)
        return ((points[-1][1] - points[half][1])
                / max(points[-1][0] - points[half][0], 1e-9))

    def t90(points, target):
        """Earliest time the W-batch sliding rate reaches ``target``;
        the run window when it never does (the honest clip)."""
        for i in range(W, len(points)):
            dt = points[i][0] - points[i - W][0]
            dr = points[i][1] - points[i - W][1]
            if dt > 0 and dr / dt >= target:
                return points[i][0]
        return points[-1][0]

    # profile-building pass: converge once (longer window - the climb has
    # to finish for the profile to record the optimum) and persist it
    loc = os.path.join(tmp, "planner_profiles")
    run(loc, duration=10.0, workers_count="auto", autotune=policy(True))

    explore_t90s, planned_t90s = [], []
    for _ in range(3):  # interleaved pairs: drift hits both arms equally
        planned_pts = run(loc, workers_count="auto", autotune=policy(True))
        explore_pts = run(os.path.join(tmp, "planner_none"),
                          workers_count="auto", autotune=policy(False))
        target = 0.9 * steady(planned_pts)  # shared peak, per pair
        planned_t90s.append(t90(planned_pts, target))
        explore_t90s.append(t90(explore_pts, target))
    explore, planned = _median(explore_t90s), _median(planned_t90s)
    _emit("planner_time_to_90pct_seconds", planned, "s", 1.0,
          note=f"planner-seeded cold start under a 30ms/read latent store"
               f" (profile at {loc}); the explore-from-static-defaults arm"
               f" took {explore:.2f}s to the same target in the same"
               " session (clipped at the 6s window when never reached)")
    return _emit(
        "planner_cold_start_ratio", explore / max(planned, 1e-9), "x", 1.0,
        note="explore-from-default t90 over planner-seeded t90 to a SHARED"
             " 90%-of-planned-steady target (median-of-3 interleaved pairs,"
             " 30ms/read object-store cost model): the flight profile jumps"
             " the worker plane straight to its converged width while the"
             " runtime loop climbs one judged move at a time; absolute"
             " floor 1.2 (bench_compare)")


# -- config: disaggregated ingest service -------------------------------------

def bench_service(tmp):
    """Disaggregated ingest A/B on the imagenet shape (ISSUEs 9+12): a
    remote fleet (dispatcher + 2 worker subprocesses, v2 binary wire
    frames) serving one trainer client vs the same read through an
    in-process thread pool; where the shm arena plane is live (py>=3.12) a
    second fleet with ``--shm-size-mb``-armed workers prices the co-located
    descriptor-only fast path too.  The ratios are SAME-SESSION anchored
    (both sides share one process/host/minute, so they are drift-immune)
    and floor-gated by tools/bench_compare.py: remote >= 0.7x, co-located
    shm >= 0.9x (ISSUE 12 acceptance; the pickled wire of r08 measured
    0.36x)."""
    import re as _re
    import subprocess
    import sys as _sys

    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.service.protocol import (connect_frames, parse_address,
                                                shm_transport_available)

    url = _ensure_imagenet(tmp)
    n_rows, epochs = 256, 3

    def one_read(**kwargs):
        t0 = time.perf_counter()
        with make_batch_reader(url, shuffle_row_groups=False,
                               num_epochs=epochs, **kwargs) as r:
            rows = sum(b.num_rows for b in r.iter_batches())
        assert rows == n_rows * epochs, rows
        return rows / (time.perf_counter() - t0)

    def stats_probe(addr):
        conn = connect_frames(parse_address(addr), timeout=5.0)
        try:
            conn.send({"t": "stats?"})
            return conn.recv(timeout=5.0)["stats"]
        finally:
            conn.close()

    def run_fleet(shm_mb: int):
        """(service rate, in-process anchor rate, dispatcher counters)
        through a fresh CLI dispatcher + 2 CLI worker subprocesses - the
        production topology, every plane its own process (shm_mb > 0 arms
        the co-located fast path).

        The two sides are measured INTERLEAVED (A/B pairs, median-of-3
        each) like bench_determinism: this box's CPU budget drifts within
        a session, so back-to-back pairs are what keep the ratio
        drift-immune.  Fleet concurrency matches the anchor's
        (capacity 1 x 2 workers = 2 concurrent decodes = workers_count=2):
        on a host where decode saturates the cores, over-subscribing the
        fleet only adds cache thrash and would bill scheduler noise to the
        transport."""
        # the fleet runs with a CLEAN allocator env: this bench process's
        # MALLOC_* pooling tuning (set at re-exec for the in-process decode
        # plane) measurably slows the fleet's frame buffers, and a real
        # deployment's dispatcher/workers never inherit a trainer's env
        fleet_env = {k: v for k, v in os.environ.items()
                     if not k.startswith("MALLOC_")}
        procs = []
        disp = subprocess.Popen(
            [_sys.executable, "-m", "petastorm_tpu.service.cli",
             "dispatcher", "--host", "127.0.0.1", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=fleet_env)
        procs.append(disp)
        try:
            line = disp.stdout.readline()
            addr = _re.search(r"listening on (\S+)", line).group(1)
            procs.extend(subprocess.Popen(
                [_sys.executable, "-m", "petastorm_tpu.service.cli",
                 "worker", "--address", addr, "--capacity", "1", "--name",
                 f"bench-w{shm_mb}-{i}", "--shm-size-mb", str(shm_mb)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=fleet_env)
                for i in range(2))
            deadline = time.monotonic() + 30
            while len(stats_probe(addr)["workers"]) < 2:
                assert time.monotonic() < deadline, "fleet never registered"
                time.sleep(0.1)
            one_read(service_address=addr)  # warmup: handles, lazy opens
            one_read(reader_pool_type="thread", workers_count=2)
            service_rates, anchor_rates = [], []
            for _ in range(3):
                anchor_rates.append(
                    one_read(reader_pool_type="thread", workers_count=2))
                service_rates.append(one_read(service_address=addr))
            counters = stats_probe(addr)["counters"]
        finally:
            for p in procs:
                p.kill()
        return _median(service_rates), _median(anchor_rates), counters

    service, inproc, counters = run_fleet(shm_mb=0)
    pkl = int(counters.get("service.frames_pickle_fallback", 0))
    _emit("service_ingest_samples_per_sec", service, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note=f"dispatcher + 2 remote worker subprocesses, v2 binary wire"
               f" ({int(counters.get('service.frames_binary', 0))} binary"
               f" frames, {pkl} pickle fallbacks);"
               f" {int(counters.get('service.completed_items', 0))} items"
               " through the fleet")
    _emit("service_inprocess_anchor_samples_per_sec", inproc, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note="same read through the in-process thread pool, interleaved"
               " A/B with the service reads (the same-session anchor the"
               " ratios divide by)")
    ratio = _emit(
        "service_vs_inprocess_ratio", service / inproc, "x", 0.35,
        note="remote fleet over in-process pool, same session"
             " (drift-immune); the v2 binary wire replaced r08's pickled"
             " frames (0.36x - serialization tax on ~5MB pixel batches)"
             " with schema'd column frames the dispatcher relays as opaque"
             " bytes; absolute floor 0.7x (bench_compare)")
    if shm_transport_available():
        colo, colo_anchor, colo_counters = run_fleet(shm_mb=512)
        _emit("service_colocated_vs_inprocess_ratio", colo / colo_anchor,
              "x", 0.35,
              note="shm-armed co-located fleet over in-process pool"
                   " (interleaved): batches cross the socket as descriptors"
                   f" only ({int(colo_counters.get('service.frames_shm', 0))}"
                   " shm frames); absolute floor 0.9x (bench_compare)")
    else:
        print("service_colocated_vs_inprocess_ratio skipped: shm transport"
              " plane unavailable on this runtime (python >= 3.12 +"
              " native lib required); the py3.12 CI job exercises it")
    return ratio


def bench_trace_overhead(tmp):
    """Per-item DISTRIBUTED tracing A/B on the service plane (ISSUE 19):
    the same fleet read (dispatcher + 2 worker subprocesses) with
    ``trace_items=8`` armed vs tracing off, interleaved back-to-back pairs
    (median-of-5 each) so the ratio is SAME-SESSION anchored and
    drift-immune.  Arming adds a trace-context dict to 1-in-8 wire items,
    per-hop monotonic stamps at dispatcher/worker, and client-side span
    merge + ``service.hop.*`` histogram recording; the acceptance bar is
    <= 2%% overhead, so ``service_trace_armed_vs_untraced_ratio`` carries
    an ABSOLUTE floor of 0.98 in tools/bench_compare.py."""
    import re as _re
    import subprocess
    import sys as _sys

    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.service.protocol import connect_frames, parse_address
    from petastorm_tpu.telemetry import Telemetry

    url = _ensure_imagenet(tmp)
    n_rows, epochs = 256, 3

    def one_read(**kwargs):
        # both arms run with a live recorder: trace_items would otherwise
        # auto-enable a private Telemetry and the ratio would price ALL of
        # telemetry (stage spans, counters) instead of the tracing increment
        t0 = time.perf_counter()
        with make_batch_reader(url, shuffle_row_groups=False,
                               num_epochs=epochs, telemetry=Telemetry(),
                               **kwargs) as r:
            rows = sum(b.num_rows for b in r.iter_batches())
        assert rows == n_rows * epochs, rows
        return rows / (time.perf_counter() - t0)

    def stats_probe(addr):
        conn = connect_frames(parse_address(addr), timeout=5.0)
        try:
            conn.send({"t": "stats?"})
            return conn.recv(timeout=5.0)["stats"]
        finally:
            conn.close()

    # fleet processes run with a CLEAN allocator env (see bench_service)
    fleet_env = {k: v for k, v in os.environ.items()
                 if not k.startswith("MALLOC_")}
    procs = []
    disp = subprocess.Popen(
        [_sys.executable, "-m", "petastorm_tpu.service.cli",
         "dispatcher", "--host", "127.0.0.1", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=fleet_env)
    procs.append(disp)
    try:
        line = disp.stdout.readline()
        addr = _re.search(r"listening on (\S+)", line).group(1)
        procs.extend(subprocess.Popen(
            [_sys.executable, "-m", "petastorm_tpu.service.cli",
             "worker", "--address", addr, "--capacity", "1", "--name",
             f"trace-w{i}"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=fleet_env)
            for i in range(2))
        deadline = time.monotonic() + 30
        while len(stats_probe(addr)["workers"]) < 2:
            assert time.monotonic() < deadline, "fleet never registered"
            time.sleep(0.1)
        one_read(service_address=addr)  # warmup: handles, lazy opens
        # median-of-5 pairs: the 0.98 floor leaves only 2 points of
        # headroom, and this 1-core box drifts +-3% between single pairs
        traced_rates, plain_rates = [], []
        for _ in range(5):
            plain_rates.append(one_read(service_address=addr))
            traced_rates.append(
                one_read(service_address=addr, trace_items=8))
    finally:
        for p in procs:
            p.kill()
    traced, plain = _median(traced_rates), _median(plain_rates)
    _emit("service_trace_armed_samples_per_sec", traced, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note="fleet read with trace_items=8 armed (1-in-8 items carry"
               " trace context + per-hop stamps through the v2 wire)")
    _emit("service_untraced_anchor_samples_per_sec", plain, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note="same fleet read with tracing off, interleaved A/B with the"
               " traced reads (the same-session anchor)")
    return _emit(
        "service_trace_armed_vs_untraced_ratio", traced / plain, "x", 1.0,
        note="armed distributed tracing over untraced, same fleet + same"
             " session (drift-immune); trace context is a ~5-element list"
             " per sampled item, stamps are perf_counter_ns appends;"
             " absolute floor 0.98 = the <=2% overhead acceptance bar"
             " (bench_compare)")


# -- config: closed-loop fleet autoscaling (ISSUE 14) --------------------------

def bench_autoscale_fleet(tmp):
    """Closed-loop autoscaling A/B on the imagenet shape (ISSUE 14): an
    UNDERSIZED fleet (1 worker) watched by a live AutoscaleSupervisor vs a
    statically right-sized fleet (2 workers), same dispatcher topology
    (CLI subprocesses) and the same read.  The supervisor must detect the
    starved client, spawn the second worker mid-read, and the whole run -
    *including* the undersized reaction window - must land within 0.8x of
    the fleet that was sized right from the start
    (``autoscale_vs_static_ratio``, ABSOLUTE floor 0.8 in
    tools/bench_compare.py).  Shutdown then retires every spawned worker
    gracefully (force-kills fail the bench).  The ratio is SAME-SESSION
    anchored: both fleets run in one process/host/minute."""
    import re as _re
    import subprocess
    import sys as _sys

    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.service.autoscale import (AutoscalePolicy,
                                                 AutoscaleSupervisor,
                                                 SubprocessSpawner)
    from petastorm_tpu.service.protocol import connect_frames, parse_address

    url = _ensure_imagenet(tmp)
    n_rows, epochs = 256, 24

    def one_read(addr):
        t0 = time.perf_counter()
        with make_batch_reader(url, shuffle_row_groups=False,
                               num_epochs=epochs,
                               service_address=addr) as r:
            rows = sum(b.num_rows for b in r.iter_batches())
        assert rows == n_rows * epochs, rows
        return rows / (time.perf_counter() - t0)

    def stats_probe(addr):
        conn = connect_frames(parse_address(addr), timeout=5.0)
        try:
            conn.send({"t": "stats?"})
            return conn.recv(timeout=5.0)["stats"]
        finally:
            conn.close()

    # fleet processes run with a CLEAN allocator env (see bench_service)
    fleet_env = {k: v for k, v in os.environ.items()
                 if not k.startswith("MALLOC_")}

    def start_dispatcher():
        disp = subprocess.Popen(
            [_sys.executable, "-m", "petastorm_tpu.service.cli",
             "dispatcher", "--host", "127.0.0.1", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=fleet_env)
        addr = _re.search(r"listening on (\S+)",
                          disp.stdout.readline()).group(1)
        return disp, addr

    def wait_workers(addr, n):
        deadline = time.monotonic() + 30
        while len(stats_probe(addr)["workers"]) < n:
            assert time.monotonic() < deadline, "fleet never registered"
            time.sleep(0.05)

    # -- side A: statically right-sized (2 workers from t=0) ------------------
    procs = []
    try:
        disp, addr = start_dispatcher()
        procs.append(disp)
        procs.extend(subprocess.Popen(
            [_sys.executable, "-m", "petastorm_tpu.service.cli", "worker",
             "--address", addr, "--capacity", "1",
             "--name", f"static-{i}"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=fleet_env) for i in range(2))
        wait_workers(addr, 2)
        one_read(addr)  # warmup: file cache, lazy opens (both sides share)
        static = _median([one_read(addr) for _ in range(3)])
    finally:
        for p in procs:
            p.kill()

    # -- side B: 1-worker floor + the live closed loop ------------------------
    # THREE independent rounds, each a FRESH undersized fleet whose
    # supervisor must detect the starved client and spawn the second
    # worker during the measured read - a single long-lived fleet would
    # only pay the reaction window on its first read and the median would
    # price steady state, not the loop.  Windows sized like a real
    # deployment scaled to this read's seconds (not the multi-second
    # production defaults): the loop still needs SUSTAINED pressure
    # (2 polls) and still settles after the event.
    auto_rates = []
    totals = {"workers_spawned": 0, "scale_ups": 0,
              "workers_retired": 0, "workers_force_killed": 0}
    for _round in range(3):
        disp2 = None
        try:
            disp2, addr2 = start_dispatcher()
            policy = AutoscalePolicy(min_workers=1, max_workers=2,
                                     poll_interval_s=0.25, grow_windows=2,
                                     shrink_windows=1000, settle_s=1.0,
                                     worker_capacity=1,
                                     starved_threshold=0.02,
                                     drain_timeout_s=20.0)
            supervisor = AutoscaleSupervisor(
                addr2, policy=policy,
                spawner=SubprocessSpawner(addr2, capacity=1, env=fleet_env))
            supervisor.start()
            wait_workers(addr2, 1)  # the min_workers floor is bring-up,
            #                         not reaction: measure from 1 worker
            auto_rates.append(one_read(addr2))
            supervisor.stop()  # graceful retire of everything it spawned
            counters = supervisor.summary()["counters"]
        finally:
            if disp2 is not None:
                disp2.kill()
        assert counters["workers_spawned"] >= 2, counters  # floor + grow
        # the floor bring-up is itself one scale_up event; >= 2 proves a
        # PRESSURE-driven grow fired during the measured read
        assert counters["scale_ups"] >= 2, counters
        assert counters["workers_force_killed"] == 0, counters
        assert counters["workers_retired"] >= 2, counters  # shutdown drain
        for k in totals:
            totals[k] += int(counters[k])
    auto = _median(auto_rates)

    _emit("autoscale_fleet_samples_per_sec", auto, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note=f"median of 3 FRESH 1-worker fleets, each growing to 2"
               f" mid-read ({totals['scale_ups']} scale-ups,"
               f" {totals['workers_spawned']} spawned,"
               f" {totals['workers_retired']} gracefully retired,"
               " 0 force-killed across the rounds)")
    _emit("autoscale_static_anchor_samples_per_sec", static, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note="statically right-sized fleet (2 workers from t=0), same"
               " session (the anchor the ratio divides by)")
    return _emit(
        "autoscale_vs_static_ratio", auto / static, "x", 0.8,
        note="closed-loop fleet (incl. its undersized reaction window)"
             " over a fleet sized right from the start; prices the"
             " supervisor's detect->spawn->register latency; ABSOLUTE"
             " floor 0.8x (bench_compare)")


# -- config: deterministic delivery -------------------------------------------

def bench_determinism(tmp):
    """Seed-stable delivery A/B on the imagenet shape (ISSUE 10): the same
    shuffled multi-worker read with ``deterministic='seed'`` (plan-order
    reorder stage + stream certificate) vs ``'off'`` (completion order).
    The ratio prices the reorder-stage tax - mostly head-of-line waiting on
    the slowest in-flight rowgroup - and is SAME-SESSION anchored
    (drift-immune).  Interleaved median-of-3 per side; gate: >= 0.85x
    (tools/bench_compare.py enforces the absolute floor)."""
    from petastorm_tpu.reader import make_batch_reader

    url = _ensure_imagenet(tmp)
    n_rows, epochs = 256, 3

    def one(mode):
        t0 = time.perf_counter()
        with make_batch_reader(url, reader_pool_type="thread",
                               workers_count=4, shuffle_row_groups=True,
                               shuffle_seed=7, deterministic=mode,
                               num_epochs=epochs) as r:
            rows = sum(b.num_rows for b in r.iter_batches())
            digest = r.diagnostics["stream_digest"]["combined"]
        assert rows == n_rows * epochs, rows
        return rows / (time.perf_counter() - t0), digest

    one("seed")  # warmup (file cache, thread spinup)
    pairs = [(one("seed"), one("off")) for _ in range(3)]
    det = _median([d for (d, _), _ in pairs])
    off = _median([o for _, (o, _) in pairs])
    digests = {d for (_, d), _ in pairs}
    assert len(digests) == 1, f"seed-mode digests diverged: {digests}"
    _emit("determinism_ingest_samples_per_sec", det, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note="deterministic='seed' (plan-order release + certificate),"
               " 4 thread workers, shuffled; digest identical across the 3"
               " rounds")
    _emit("determinism_off_anchor_samples_per_sec", off, "samples/sec",
          R2["imagenet_ingest_samples_per_sec"],
          note="same read, completion-order delivery (the same-session"
               " anchor the ratio divides by)")
    return _emit("determinism_vs_off_ratio", det / off, "x", 0.85,
                 note="reorder-stage tax: head-of-line wait on the slowest"
                      " in-flight rowgroup (honestly noted - 'off' hands"
                      " the consumer whatever finished first); gated at"
                      " an ABSOLUTE >= 0.85x floor by bench_compare, not"
                      " just baseline drift")


# -- config: sequence packing (ISSUE 11) --------------------------------------

def bench_sequence_packing(tmp):
    """Token pipeline A/B (ISSUE 11): packed ``(batch, seq_len)`` delivery
    vs the naive pad-to-max baseline on a north-star-shaped token corpus
    (lognormal doc lengths - the long-tail shape real corpora have).

    Both sides read the SAME corpus through the same seeded reader and pay
    the same decode; both run the same per-block consumer - a touch of
    every slot plus a fixed simulated train step per ``(batch, seq_len)``
    block (the ``--simulated-step-ms`` idiom from the throughput harness:
    a jit step's cost is a function of the static block shape, pad or
    real, which is exactly what packing amortizes).  Useful-tokens/s =
    real (non-pad) tokens delivered / wall time; the ratio is SAME-SESSION
    anchored (drift-immune) and gated at an ABSOLUTE >= 1.5x floor, with
    fill-rate gated >= 0.85 (tools/bench_compare.py)."""
    import numpy as np

    from petastorm_tpu.sequence import iter_documents, iter_packed_blocks
    from petastorm_tpu.sequence.packing import SequencePacker
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.test_util.synthetic import write_token_corpus

    url = os.path.join(tmp, "token_corpus")
    seq_len, block_rows, n_docs = 1024, 8, 8192
    step_s = 0.004  # simulated per-block train step (4 ms per (8, 1024))
    total_tokens = write_token_corpus(
        url, n_docs=n_docs, rows_per_rg=512, vocab=32000, mean_len=180.0,
        min_len=8, max_len=2048, seed=11, label_field=None)

    def open_reader():
        return make_batch_reader(url, reader_pool_type="thread",
                                 workers_count=4, shuffle_row_groups=True,
                                 shuffle_seed=7, num_epochs=1)

    def consume(block):
        # the consumer model: touch every slot (forces materialization)
        # then pay a FIXED step cost per block - a jit train step compiles
        # for the static (batch, seq_len) shape and costs the same whether
        # a slot holds a real token or padding
        sink = int(block["tokens"].sum()) + int(block["loss_mask"].sum())
        time.sleep(step_s)
        return sink

    def run_packed():
        sink = 0
        t0 = time.perf_counter()
        with open_reader() as reader:
            packer = SequencePacker(seq_len)
            for block in iter_packed_blocks(
                    iter_documents(reader, "tokens"), seq_len, block_rows,
                    packer=packer):
                sink += consume(block)
            stats = packer.stats()
        dt = time.perf_counter() - t0
        assert stats["tokens"] == total_tokens, (stats, total_tokens)
        return stats["tokens"] / dt, stats["fill_rate"], sink

    def run_padded():
        # the naive baseline: one document per row, padded to seq_len
        # (long docs truncate - pad-to-max cannot split); same reader,
        # same consumer
        sink = 0
        real = 0
        t0 = time.perf_counter()
        with open_reader() as reader:
            pend_t = np.zeros((block_rows, seq_len), dtype=np.int32)
            pend_m = np.zeros((block_rows, seq_len), dtype=np.float32)
            fill = 0
            for doc in iter_documents(reader, "tokens"):
                n = min(len(doc), seq_len)
                if n == 0:
                    continue
                pend_t[fill, :n] = doc[:n]
                pend_t[fill, n:] = 0
                pend_m[fill, :n] = 1.0
                pend_m[fill, n:] = 0.0
                real += n
                fill += 1
                if fill == block_rows:
                    sink += consume({"tokens": pend_t, "loss_mask": pend_m})
                    fill = 0
            if fill:
                sink += consume({"tokens": pend_t[:fill],
                                 "loss_mask": pend_m[:fill]})
        dt = time.perf_counter() - t0
        return real / dt, real, sink

    run_packed()  # warmup (file cache, thread spinup)
    packed_rates, fills, padded_rates = [], [], []
    padded_real = total_tokens
    for _ in range(3):
        rate, fill, _ = run_packed()
        packed_rates.append(rate)
        fills.append(fill)
        rate, padded_real, _ = run_padded()
        padded_rates.append(rate)
    packed = _median(packed_rates)
    padded = _median(padded_rates)
    fill = _median(fills)
    _emit("sequence_packed_tokens_per_sec", packed, "tokens/sec", padded,
          note=f"first-fit packed ({block_rows}, {seq_len}) blocks, 4"
               " thread workers, seeded shuffle; useful (non-pad) tokens"
               " over end-to-end wall time incl. decode + a 4 ms simulated"
               " step per block; vs_baseline IS the packed/padded ratio"
               " (same-session anchor)")
    _emit("sequence_padded_anchor_tokens_per_sec", padded, "tokens/sec",
          padded,
          note="naive pad-to-max baseline: one doc per row padded to"
               f" seq_len={seq_len} (long docs truncate to"
               f" {padded_real}/{total_tokens} deliverable tokens), same"
               " reader + consumer - the same-session anchor the ratio"
               " divides by")
    _emit("sequence_packing_fill_rate", fill, "fraction", 0.85,
          note="real tokens / emitted slots on the lognormal corpus"
               " (mean 180 tokens, seq_len 1024); gated at an ABSOLUTE"
               " >= 0.85 floor by bench_compare")
    return _emit("sequence_packed_vs_padded_ratio", packed / padded, "x",
                 1.5,
                 note="useful-tokens/s, packed over pad-to-max, both under"
                      " a 4 ms simulated step per block; honest accounting"
                      " - both sides pay the same (serial, un-overlapped)"
                      " corpus decode, which dilutes the ratio below the"
                      " pure step-count win (fill*seq_len/mean_len ~= 5.6x"
                      " here); with a 0 ms step both sides are decode-bound"
                      " and the ratio is ~1. Gated at an ABSOLUTE >= 1.5x"
                      " floor by bench_compare")


# -- config 5: ngram windows --------------------------------------------------

def bench_ngram(tmp):
    import numpy as np

    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.ngram import NGram
    from petastorm_tpu.reader import make_reader
    from petastorm_tpu.schema import Field, Schema

    url = os.path.join(tmp, "seq")
    schema = Schema("Seq", [
        Field("ts", np.int64, (), ScalarCodec()),
        Field("cam", np.uint8, (32, 32, 3), NdarrayCodec()),
    ])
    rng = np.random.default_rng(5)
    rows = [{"ts": i,
             "cam": rng.integers(0, 255, (32, 32, 3), dtype=np.uint8)}
            for i in range(8192)]
    write_dataset(url, schema, rows, row_group_size_rows=512)

    ng = NGram({0: ["ts", "cam"], 1: ["ts", "cam"], 2: ["ts", "cam"]},
               delta_threshold=1, timestamp_field="ts")

    def run():
        wins = 0
        with make_reader(url, ngram=ng, reader_pool_type="serial",
                         num_epochs=1, shuffle_row_groups=False) as r:
            t0 = time.perf_counter()
            for b in r.iter_batches():
                wins += b.num_rows
            return wins / (time.perf_counter() - t0)

    run()
    rate = _median([run() for _ in range(3)])
    return _emit("ngram_windows_per_sec", rate, "windows/sec",
                 R2["ngram_windows_per_sec"],
                 note="median-of-3 vs round-2 recorded max-of-3"
                      + _ceiling_note(rate, url))


def main() -> None:
    import shutil
    import traceback

    from petastorm_tpu.jax.compile_cache import enable_compile_cache

    enable_compile_cache()  # sets config only; no backend starts here
    tmp = tempfile.mkdtemp(prefix="petastorm_tpu_bench_")
    try:
        # non-headline configs are isolated: a failure (chip runtime down,
        # native lib missing, ...) must not suppress the driver-parsed
        # HEADLINE line.  The two train configs run FIRST: their subprocess
        # measurements need exclusive chip ownership, so the parent must not
        # have initialized the device runtime yet.
        for fn in (bench_train_stall, bench_north_star_train,
                   bench_cold_floor, bench_mnist, bench_imagenet,
                   bench_imagenet_mixed, bench_converter, bench_ngram,
                   bench_remote_latency, bench_north_star, bench_autotune,
                   bench_warm_cache, bench_transform_cache,
                   bench_planner_cold_start, bench_service,
                   bench_trace_overhead,
                   bench_autoscale_fleet, bench_determinism,
                   bench_sequence_packing):
            try:
                fn(tmp)
            except Exception:  # noqa: BLE001 - reported, never fatal
                print(json.dumps({"metric": fn.__name__, "error":
                                  traceback.format_exc(limit=3)}), flush=True)
        # penultimate summary: replay every metric in ONE line directly before
        # the headline, so any tail window of the driver's capture holds all
        # numbers even if early lines scrolled out (BENCH_r03 truncation)
        print(json.dumps({"metric": "bench_summary",
                          "metrics": {ln["metric"]: [ln["value"],
                                                     ln["vs_baseline"]]
                                      for ln in _EMITTED}}), flush=True)
        bench_hello_world(tmp)  # headline LAST: the driver parses the last line
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
