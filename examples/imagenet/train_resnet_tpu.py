"""ImageNet-style ResNet-50 training feed on TPU: the flagship benchmark path.

Reference parity: examples/imagenet/ (petastorm ImageNet dataset + pytorch
feed).  TPU re-design: JPEG-compressed images are stored via
CompressedImageCodec, decoded by host workers, shipped as uint8 (1 byte/pixel
over PCIe/DCN), normalized ON-CHIP (ops.normalize_images, fused by XLA into
the first conv), and the global batch is sharded over the mesh's 'data' axis
by the loader.  Run with --steps/--rows sized for your pod; the defaults are
smoke-test sized.

This is the BASELINE.md north-star shape: samples/sec/chip feeding ResNet-50.
"""

import argparse
import os
import queue
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.jax import JaxDataLoader
from petastorm_tpu.models import ResNet50
from petastorm_tpu.ops import (normalize_images, random_flip,
                               random_resized_crop)
from petastorm_tpu.reader import make_reader
from petastorm_tpu.schema import Field, Schema


def imagenet_schema(side: int) -> Schema:
    return Schema("ImagenetLike", [
        Field("id", np.int64, (), ScalarCodec()),
        Field("label", np.int64, (), ScalarCodec()),
        Field("image", np.uint8, (side, side, 3),
              CompressedImageCodec("jpeg", quality=90)),
    ])


def generate_dataset(url: str, rows: int, side: int, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    schema = imagenet_schema(side)

    def row(i):
        label = int(rng.integers(0, 1000))
        base = rng.integers(0, 255, (side, side, 3)).astype(np.uint8)
        return {"id": i, "label": label, "image": base}

    write_dataset(url, schema, (row(i) for i in range(rows)),
                  row_group_size_rows=max(rows // 8, 1), mode="overwrite")


def build_tfrecord(dataset_url: str, tfr_path: str) -> None:
    """Extract the STORED jpeg bytes from the parquet dataset into a TFRecord
    so the tf.data comparator reads its native format with zero parquet
    overhead (best effort for tf.data; same bytes, same decode work)."""
    import pyarrow.dataset as pads
    import tensorflow as tf

    table = pads.dataset(dataset_url, format="parquet").to_table(
        columns=["label", "image"])
    # write-then-rename: an interrupted build must not leave a truncated
    # .tfrecord that a later 'if exists' check happily reuses
    tmp_path = tfr_path + ".tmp"
    with tf.io.TFRecordWriter(tmp_path) as w:
        for b, lbl in zip(table.column("image").to_pylist(),
                          table.column("label").to_pylist()):
            ex = tf.train.Example(features=tf.train.Features(feature={
                "image": tf.train.Feature(
                    bytes_list=tf.train.BytesList(value=[b])),
                "label": tf.train.Feature(
                    int64_list=tf.train.Int64List(value=[int(lbl)]))}))
            w.write(ex.SerializeToString())
    os.replace(tmp_path, tfr_path)


class TfdataDeviceFeed:
    """tf.data comparator for the north-star A/B: TFRecord -> decode_jpeg ->
    batch -> prefetch(AUTOTUNE), plus a background device-transfer thread
    (depth = ``prefetch``) so both pipelines overlap host->device copies with
    compute - the A/B then measures the INPUT pipelines, not a strawman
    synchronous ``device_put`` on the tf.data consumer path.

    Mirrors JaxDataLoader's consumer contract: ``next()`` yields a dict of
    ready device arrays and ``consumer_wait_s`` accumulates the time the
    consumer spent blocked waiting for input.
    """

    def __init__(self, tfr_path: str, global_batch: int, prefetch: int,
                 image_sharding, label_sharding):
        import tensorflow as tf

        feat = {"image": tf.io.FixedLenFeature([], tf.string),
                "label": tf.io.FixedLenFeature([], tf.int64)}

        def _parse(raw):
            ex = tf.io.parse_single_example(raw, feat)
            return tf.io.decode_jpeg(ex["image"], channels=3), ex["label"]

        ds = (tf.data.TFRecordDataset(tfr_path).repeat()
                .map(_parse, num_parallel_calls=tf.data.AUTOTUNE,
                     deterministic=False)
                .batch(global_batch, drop_remainder=True)
                .prefetch(tf.data.AUTOTUNE))
        self._it = ds.as_numpy_iterator()
        self._image_sharding = image_sharding
        self._label_sharding = label_sharding
        self._q: "queue.Queue" = queue.Queue(maxsize=max(prefetch, 1))
        self._stop = threading.Event()
        self.consumer_wait_s = 0.0
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="tfdata-device-feed")
        self._thread.start()

    def _produce(self):
        try:
            while not self._stop.is_set():
                img, lbl = next(self._it)
                batch = {"image": jax.device_put(img, self._image_sharding),
                         "label": jax.device_put(lbl, self._label_sharding)}
                jax.block_until_ready(batch)  # commit in the transfer thread
                while not self._stop.is_set():
                    try:
                        self._q.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as exc:  # noqa: BLE001 - re-raised in __next__
            # a silently-dead producer would block the consumer forever on
            # q.get(); ship the error as a sentinel instead (without blocking
            # past shutdown if the consumer is already gone)
            while not self._stop.is_set():
                try:
                    self._q.put(("__error__", exc), timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        batch = self._q.get()
        self.consumer_wait_s += time.perf_counter() - t0
        if isinstance(batch, tuple) and batch and batch[0] == "__error__":
            raise RuntimeError("tf.data feed producer failed") from batch[1]
        return batch

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)


def make_train_step(mesh: Mesh, side: int, num_classes: int = 1000,
                    seed: int = 0):
    """The flagship consumer: ResNet-50 (bf16) with SGD + momentum, params
    replicated over ``mesh`` and the batch sharded over its first axis.

    Returns ``(params, opt_state, train_step, train_scan)``.  Each step runs
    the ImageNet train transform on-chip - random-resized-crop, flip, then
    the uint8 -> bf16 normalize - so host workers stay decode-only."""
    model = ResNet50(num_classes=num_classes)
    # one compiled init, not hundreds of op-by-op dispatches
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 jnp.zeros((1, side, side, 3), jnp.bfloat16))
    # replicate params across the mesh; batch is sharded over 'data'
    params = jax.device_put(params, NamedSharding(mesh, P()))
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def _step_math(p, o, image_u8, label, key):
        def loss_fn(pp):
            k1, k2 = jax.random.split(key)
            # per-image RandomResizedCrop (scale/ratio sampling, one
            # static-shape kernel), flip, then uint8 -> bf16 normalize
            imgs = random_resized_crop(image_u8, k1, (side, side))
            imgs = random_flip(imgs, k2)
            x = normalize_images(imgs)          # on-chip uint8 -> bf16 + scale
            logits = model.apply(pp, x)
            onehot = jax.nn.one_hot(label, num_classes)
            return -(jax.nn.log_softmax(logits) * onehot).sum(-1).mean()

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    @jax.jit
    def train_step(params, opt_state, image_u8, label, key):
        return _step_math(params, opt_state, image_u8, label, key)

    @jax.jit
    def train_scan(params, opt_state, images_u8, labels, key):
        """scan_steps train steps in ONE dispatch (images_u8: (K, B, H, W, 3)).

        Each jit call pays a fixed dispatch cost; lax.scan amortizes it
        K-fold.  Same math as train_step - scan carries (params, opt_state,
        key).
        """
        def body(carry, xs):
            p, o, k = carry
            img, lbl = xs
            k, sub = jax.random.split(k)
            p, o, loss = _step_math(p, o, img, lbl, sub)
            return (p, o, k), loss

        (params, opt_state, _), losses = jax.lax.scan(
            body, (params, opt_state, key), (images_u8, labels))
        return params, opt_state, losses[-1]

    return params, opt_state, train_step, train_scan


def make_feed(dataset_url: str, mesh: Mesh, global_batch: int,
              decode: str = "device", workers: int = 4, prefetch: int = 2,
              cache: str = "null", scan_steps: int = 1,
              pool_type: str = "thread") -> JaxDataLoader:
    """The real ingest stack into ``mesh``: ``make_reader`` ->
    ``JaxDataLoader`` with the batch sharded ``P("data")``.

    ``decode='device'`` is hybrid jpeg decode: host workers run only the
    entropy decode, dequant + IDCT + upsample + color run on-chip
    (ops/jpeg.py).  It needs the native image library and fails without it
    rather than fall back to a host decode."""
    if decode == "device":
        from petastorm_tpu.native import image as native_image

        if not native_image.available():
            raise RuntimeError(
                "decode='device' needs the native image library, which is"
                " unavailable; build it with: " + native_image.BUILD_COMMAND)
    placement = {"image": "device"} if decode == "device" else None
    # cache='memory' keeps decoded (or entropy-decoded, for decode='device')
    # batches in a host LRU: epochs after the first skip parquet+jpeg work
    # entirely - the answer for datasets that fit RAM
    reader = make_reader(dataset_url, num_epochs=None, workers_count=workers,
                         reader_pool_type=pool_type,
                         decode_placement=placement, cache_type=cache)
    # scan mode rides the loader's stacked delivery: ONE (K, B, ...)
    # transfer per K steps (stack_batches=K)
    return JaxDataLoader(reader, batch_size=global_batch, mesh=mesh,
                         prefetch=prefetch, stack_batches=max(scan_steps, 1),
                         shardings={"image": P("data"), "label": P("data")})


def train(dataset_url: str, steps: int, global_batch: int, side: int,
          num_classes: int = 1000, decode: str = "device",
          workers: int = 4, prefetch: int = 2, cache: str = "null",
          input_pipeline: str = "petastorm", scan_steps: int = 1) -> dict:
    """Run ``steps`` real ResNet-50 train steps fed by the loader; returns a
    metrics dict incl. samples/sec/chip and ``input_wait_pct`` (consumer wait
    over wall time in the measured window)."""
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("data",))
    params, opt_state, train_step, train_scan = make_train_step(
        mesh, side, num_classes)

    if input_pipeline == "tfdata":
        # the north-star comparator: SAME stored jpegs (re-packed as TFRecord,
        # tf.data's native format), SAME train_step, symmetric background
        # device transfer - only the input pipeline differs
        tfr = dataset_url.rstrip("/") + ".tfrecord"
        if not os.path.exists(tfr):
            build_tfrecord(dataset_url, tfr)
        feed = TfdataDeviceFeed(tfr, global_batch, prefetch,
                                NamedSharding(mesh, P("data")),
                                NamedSharding(mesh, P("data")))
        decode = "tfdata-host"
    else:
        feed = make_feed(dataset_url, mesh, global_batch, decode, workers,
                         prefetch, cache, scan_steps)

    def consumer_wait(f):
        # both feeds expose the same signal: seconds the consumer spent
        # blocked waiting for a ready device batch
        return (f.diagnostics["consumer_wait_s"] if hasattr(f, "diagnostics")
                else f.consumer_wait_s)

    step = 0
    with feed:
        it = iter(feed)
        aug_key = jax.random.PRNGKey(17)

        def pull_unit():
            if scan_steps <= 1 or input_pipeline != "tfdata":
                # petastorm scan mode: the loader already delivers whole
                # (K, B, ...) stacks (stack_batches=K) in one transfer
                return next(it)
            # tfdata comparator only: tf.data has no stacked delivery, so the
            # comparator pays K transfers + a stack dispatch per unit
            bs = [next(it) for _ in range(scan_steps)]
            return {"image": jnp.stack([b["image"] for b in bs]),
                    "label": jnp.stack([b["label"] for b in bs])}

        # AOT-compile the step once: the SAME executable runs the loop AND
        # reports XLA's FLOP estimate for the whole dispatch - the MFU
        # numerator comes from the compiler, not a hand-derived constant
        unit0 = pull_unit()
        fn = train_step if scan_steps <= 1 else train_scan
        exe = fn.lower(params, opt_state, unit0["image"], unit0["label"],
                       aug_key).compile()
        try:
            flops_per_dispatch = float(exe.cost_analysis()["flops"])
        except (KeyError, TypeError, IndexError):
            flops_per_dispatch = None  # backend without a cost model

        def run_unit(p, o, unit, key):
            return exe(p, o, unit["image"], unit["label"], key)

        # warmup: fill queues, settle dispatch.  Every measured window below
        # ends in a value fetch (float(loss)); the loss chains through every
        # step's params, so fetching it waits for ALL queued compute - the
        # wall times below include full device completion
        params, opt_state, loss = run_unit(params, opt_state, unit0, aug_key)
        float(loss)
        # consumer wait accumulates while the consumer blocks on the prefetch
        # queue: the delta over the measured window is the time the train
        # loop spent waiting on input during REAL train steps
        wait0 = consumer_wait(feed)
        n_disp = 0
        t0 = time.perf_counter()
        while step < steps:
            params, opt_state, loss = run_unit(params, opt_state, pull_unit(),
                                               jax.random.fold_in(aug_key, step))
            step += max(scan_steps, 1)
            n_disp += 1
        float(loss)
        dt = time.perf_counter() - t0
        input_wait_s = consumer_wait(feed) - wait0
        # compute floor: the SAME number of dispatches on one RESIDENT unit -
        # no input pipeline inside the loop, so (dt - compute_dt) is the
        # input-attributable stall.  Unlike consumer_wait, this is valid in
        # scan mode too (consumer wait there overlaps in-flight device work).
        unit_f = pull_unit()
        p2, o2 = params, opt_state
        t1 = time.perf_counter()
        for i in range(n_disp):
            p2, o2, loss2 = run_unit(p2, o2, unit_f,
                                     jax.random.fold_in(aug_key, 1 << 20 | i))
        float(loss2)
        compute_dt = time.perf_counter() - t1
        diag = feed.diagnostics if hasattr(feed, "diagnostics") else {}
    samples = step * global_batch
    # per-sample FLOPs only from the SINGLE-step lowering: XLA's cost model
    # counts a lax.scan body ONCE (verified: scan=8 reports exactly 1/8th),
    # so the scan executable's figure is not per-sample-meaningful - callers
    # wanting scan-mode MFU should take flops_per_sample from a scan=1 run
    # of the same shapes
    flops_per_sample = (flops_per_dispatch / global_batch
                        if flops_per_dispatch and scan_steps <= 1 else None)
    return {
        "flops_per_dispatch": flops_per_dispatch,
        "samples_per_sec": samples / dt,
        "samples_per_sec_per_chip": samples / dt / len(devices),
        "input_wait_pct": 100.0 * input_wait_s / dt,
        "input_stall_pct": 100.0 * max(0.0, dt - compute_dt) / dt,
        "compute_floor_wall_s": compute_dt,
        "flops_per_sample": flops_per_sample,
        "device_kind": devices[0].device_kind,
        "steps": step,
        "scan_steps": scan_steps,
        "global_batch": global_batch,
        "wall_s": dt,
        "decode": decode,
        "input": input_pipeline,
        "n_devices": len(devices),
        "final_loss": float(loss),
        "diagnostics": diag,
    }


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--dataset-url", default=None)
    parser.add_argument("--rows", type=int, default=256)
    parser.add_argument("--side", type=int, default=224)
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--global-batch", type=int, default=32)
    parser.add_argument("--decode", choices=("host", "device"), default="device",
                        help="device = hybrid on-chip jpeg decode")
    parser.add_argument("--workers", type=int, default=4)
    parser.add_argument("--prefetch", type=int, default=2)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--cache", choices=("null", "memory", "local-disk"),
                        default="null",
                        help="memory = host LRU; warm epochs skip all decode")
    parser.add_argument("--input", choices=("petastorm", "tfdata"),
                        default="petastorm",
                        help="tfdata = north-star comparator: same jpegs via"
                             " TFRecord + tf.data feeding the SAME train step")
    parser.add_argument("--scan-steps", type=int, default=1,
                        help="K>1 = run K train steps per dispatch via"
                             " lax.scan (amortizes the fixed per-call dispatch"
                             " cost)")
    parser.add_argument("--skip-generate", action="store_true",
                        help="dataset-url already holds the dataset")
    parser.add_argument("--json", action="store_true",
                        help="print the metrics dict as one JSON line")
    args = parser.parse_args()
    from petastorm_tpu.jax.compile_cache import enable_compile_cache

    enable_compile_cache()
    url = args.dataset_url or tempfile.mkdtemp(prefix="imagenet_tpu_") + "/imagenet"
    if not args.skip_generate:
        generate_dataset(url, args.rows, args.side)
    m = train(url, args.steps, args.global_batch, args.side,
              num_classes=args.num_classes, decode=args.decode,
              workers=args.workers, prefetch=args.prefetch, cache=args.cache,
              input_pipeline=args.input, scan_steps=args.scan_steps)
    if args.json:
        import json

        print(json.dumps(m))
    else:
        print(f"{m['steps'] * m['global_batch']} samples in {m['wall_s']:.2f}s"
              f" = {m['samples_per_sec']:.1f} samples/sec"
              f" ({m['samples_per_sec_per_chip']:.1f} samples/sec/chip on"
              f" {m['n_devices']} chip(s)), input wait"
              f" {m['input_wait_pct']:.1f}% of wall time, final loss"
              f" {m['final_loss']:.4f}")
