"""Image cells: a JPEG dataset made from the seed, read through
``make_reader`` -> ``JaxDataLoader`` into the configuration's consumer.

The mix's ``reader`` and ``loader`` entries go to ``make_reader`` and
``JaxDataLoader`` as they stand, so a mix that changes the decode
placement, the cache or the pool is a data file alone.
"""

from __future__ import annotations

import os
import shutil
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from benchmarks.harness import key_seed, log, seed_rng, seed_words
from benchmarks.reference import images as ref

#: the dataset is complete once this file exists
DONE = "_DONE"
#: at most this many epochs are pulled after the window for rows still due
DRAIN_EPOCHS = 10


def lookahead_row_groups(workers: int) -> int:
    """The most row groups a reader can deliver ahead of those due: its
    input queue (workers + 2), its workers and its results queue (10),
    with the loader's few queued batches and room to spare."""
    return 3 * workers + 12


def natural_image(seed: int, index: int, side: int, texture: dict):
    """One image with natural-image structure (smooth regions, hard and
    soft edges, texture), made from (seed, index) alone."""
    import cv2

    rng = np.random.default_rng([index] + seed_words(seed))
    base = rng.uniform(0, 255, (4, 4, 3)).astype(np.float32)
    img = cv2.resize(base, (side, side), interpolation=cv2.INTER_CUBIC)
    lo, hi = texture["shapes"]
    for _ in range(int(rng.integers(lo, hi))):
        color = tuple(float(c) for c in rng.uniform(0, 255, 3))
        kind = int(rng.integers(0, 3))
        c = tuple(int(v) for v in rng.integers(0, side, 2))
        if kind == 0:
            axes = tuple(int(v) for v in rng.integers(max(side // 16, 1),
                                                      max(side // 3, 2), 2))
            cv2.ellipse(img, c, axes, float(rng.uniform(0, 180)), 0, 360,
                        color, -1, cv2.LINE_AA)
        elif kind == 1:
            d = tuple(int(v) for v in rng.integers(0, side, 2))
            cv2.rectangle(img, c, d, color, -1)
        else:
            pts = rng.integers(0, side, (int(rng.integers(3, 7)), 2))
            cv2.fillPoly(img, [pts.astype(np.int32)], color, cv2.LINE_AA)

    def noise(shape, sd):
        u = np.frombuffer(rng.bytes(int(np.prod(shape))), np.uint8)
        return (u.reshape(shape).astype(np.float32) - 127.5) * (sd / 73.9)

    half = max(side // 2, 1)
    img += cv2.resize(noise((half, half, 3), texture["mid"]), (side, side),
                      interpolation=cv2.INTER_LINEAR)
    img += noise((side, side, 3), texture["fine"])
    return np.clip(img, 0, 255).astype(np.uint8)


def _encode_rows(seed: int, start: int, stop: int, data: dict):
    """JPEG bytes of rows [start, stop), as the program's
    ``CompressedImageCodec`` stores them (OpenCV, quality ``jpeg_quality``)."""
    import cv2

    cv2.setNumThreads(1)
    out = []
    for i in range(start, stop):
        img = natural_image(seed, i, data["side"], data["texture"])
        ok, enc = cv2.imencode(".jpeg", img[..., ::-1],
                               [int(cv2.IMWRITE_JPEG_QUALITY),
                                data["jpeg_quality"]])
        if not ok:
            raise RuntimeError(f"JPEG encode of row {i} failed")
        out.append(enc.tobytes())
    return out


def write_dataset(path: str, seed: int, data: dict) -> float:
    """The dataset at ``path``: ids 0..rows-1, labels and images from the
    seed.  Rows are made in spawned processes, which never touch JAX.
    Returns mean bytes per row."""
    import multiprocessing

    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import stamp_dataset_metadata
    from petastorm_tpu.schema import Field, Schema

    side, rows = data["side"], data["rows"]
    schema = Schema("ImagenetLike", [
        Field("id", np.int64, (), ScalarCodec()),
        Field("label", np.int64, (), ScalarCodec()),
        Field("image", np.uint8, (side, side, 3),
              CompressedImageCodec("jpeg", quality=data["jpeg_quality"])),
    ])
    labels = seed_rng(seed, 2).integers(0, data["num_classes"], rows)
    os.makedirs(path)
    total = 0
    chunk = 64
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=os.cpu_count() or 1,
                             mp_context=ctx) as ex:
        per_file = data["rows_per_file"]
        for f, start in enumerate(range(0, rows, per_file)):
            stop = min(start + per_file, rows)
            parts = [ex.submit(_encode_rows, seed, a, min(a + chunk, stop),
                               data) for a in range(start, stop, chunk)]
            blobs = [b for part in parts for b in part.result()]
            total += sum(len(b) for b in blobs)
            table = pa.table({
                "id": pa.array(np.arange(start, stop), pa.int64()),
                "label": pa.array(labels[start:stop], pa.int64()),
                "image": pa.array(blobs, pa.binary())})
            pq.write_table(table, os.path.join(path, f"part-{f:05d}.parquet"),
                           row_group_size=data["row_group_rows"],
                           compression="NONE")
    stamp_dataset_metadata(path, schema)
    return total / rows


class Pipeline:
    def __init__(self, config, traffic, consumer, seed, mesh, telemetry,
                 data_dir):
        self.config, self.traffic = config, traffic
        self.consumer_mod = consumer
        self.seed, self.mesh = seed, mesh
        self.batch = config["batch_per_chip"] * mesh.size
        self.data_dir = data_dir
        self.telemetry = None
        if telemetry:
            from petastorm_tpu.telemetry import Telemetry

            self.telemetry = Telemetry()
        self.loader = None
        self.consumer = None
        self.ids, self.labels, self.losses = [], [], []

    # -- set-up ---------------------------------------------------------------

    def prepare(self) -> None:
        if os.path.exists(os.path.join(self.data_dir, DONE)):
            return
        partial = self.data_dir + ".partial"
        shutil.rmtree(partial, ignore_errors=True)
        per_row = write_dataset(partial, self.seed, self.config["data"])
        log(f"dataset: {self.config['data']['rows']} rows,"
            f" {per_row:.0f} bytes per row on average")
        with open(os.path.join(partial, DONE), "w") as f:
            f.write(f"{per_row}\n")
        shutil.rmtree(self.data_dir, ignore_errors=True)
        os.replace(partial, self.data_dir)

    def open(self) -> None:
        import jax
        from jax.sharding import PartitionSpec as P

        from petastorm_tpu.jax import JaxDataLoader
        from petastorm_tpu.reader import make_reader

        self.consumer = self.consumer_mod.Consumer(
            self.config, self.mesh, self.batch, key_seed(self.seed))
        self.key = jax.random.PRNGKey(key_seed(self.seed) + 1)
        reader = make_reader(
            self.data_dir, num_epochs=None,
            shuffle_seed=int(seed_rng(self.seed, 3).integers(0, 2 ** 31)),
            telemetry=self.telemetry, **self.traffic["reader"])
        self.loader = JaxDataLoader(
            reader, batch_size=self.batch, mesh=self.mesh,
            shardings={"image": P("data"), "label": P("data"),
                       "id": P("data")},
            **self.traffic.get("loader", {}))
        self.it = iter(self.loader)

    # -- the timed path -------------------------------------------------------

    def next_batch(self):
        return next(self.it)

    def step(self, batch, i):
        import jax

        loss, seen = self.consumer(batch["image"], batch["label"],
                                   jax.random.fold_in(self.key, i))
        self.ids.append(batch["id"])
        self.labels.append(batch["label"])
        self.losses.append(loss)
        kept = {"id": batch["id"], "image": batch["image"],
                "crop": seen[0], "norm": seen[1]}
        return loss, kept

    def counters(self) -> dict:
        if self.telemetry is None:
            return {}
        return dict(self.telemetry.snapshot()["counters"])

    def work(self, i: int) -> float:
        return float(self.batch)

    def shapes(self) -> dict:
        d = self.config["data"]
        return {"rows": self.batch, "chips": self.mesh.size,
                "height": d["side"], "width": d["side"],
                "sampling": d["sampling"],
                "normalize_row_elems": d["side"] * d["side"] * 3}

    def programs(self) -> dict:
        return {"step": "jit_train_step", "decode": "jit_decode_coefficients"}

    # -- after the window -----------------------------------------------------

    def fetch(self, samples) -> dict:
        ids = np.concatenate([np.asarray(a) for a in self.ids])
        out = {"samples": {i: {k: np.asarray(v) for k, v in s.items()}
                           for i, s in samples.items()},
               "ids": ids, "drained": self._drain(ids),
               "labels": np.concatenate([np.asarray(a)
                                         for a in self.labels]),
               "losses": np.asarray([float(x) for x in self.losses])}
        self.ids = self.labels = self.losses = None
        return out

    def _drain(self, ids: np.ndarray) -> np.ndarray:
        """Ids of batches pulled after the window, without the step, until
        every row has come as often as the epochs delivered by then: the
        pool delivers in completion order, so a row group can come epochs
        late.  Late is not wrong; one that never comes is."""
        n_rows = self.config["data"]["rows"]
        due = len(ids) // n_rows
        counts = np.bincount(ids[(ids >= 0) & (ids < n_rows)],
                             minlength=n_rows)
        out, pulled = [], 0
        while (counts < due).any() and pulled < DRAIN_EPOCHS * n_rows:
            got = np.asarray(next(self.it)["id"]).astype(np.int64)
            out.append(got)
            pulled += len(got)
            counts += np.bincount(got[(got >= 0) & (got < n_rows)],
                                  minlength=n_rows)
        return np.concatenate(out) if out else np.zeros(0, np.int64)

    def close(self) -> None:
        if self.loader is not None:
            self.loader.__exit__(None, None, None)
            self.loader = None
        if self.consumer is not None:
            self.consumer.close()

    def _delivery_errors(self, stream: np.ndarray, n_window: int) -> int:
        """Rows delivered against the reader's guarantee, each row group
        whole and in stored order, each row once per epoch, in completion
        order: rows out of their group's order, rows still missing after
        the drain from the epochs the window delivered, and rows that came
        more often than the row groups read ahead allow."""
        n_rows = self.config["data"]["rows"]
        groups = ref.row_groups(self.data_dir)
        # the id that must come next inside its group; -1 ends a group, -2
        # marks an id outside the dataset (index n_rows)
        follow = np.full(n_rows + 1, -2, np.int64)
        start = np.zeros(n_rows + 1, bool)
        for g in groups:
            follow[g[:-1]] = g[1:]
            follow[g[-1]] = -1
            start[g[0]] = True
        s = np.where((stream >= 0) & (stream < n_rows), stream, n_rows)
        after = follow[s[:-1]]
        ok = np.where(after == -1, start[s[1:]], after == s[1:])
        broken = int((~ok).sum()) + int(not start[s[0]])
        counts = np.bincount(s, minlength=n_rows + 1)[:n_rows]
        missing = np.maximum(n_window // n_rows - counts, 0).sum()
        workers = self.traffic["reader"].get("workers_count")
        if not isinstance(workers, int):
            workers = os.cpu_count() or 1
        ahead = lookahead_row_groups(workers) * max(len(g) for g in groups)
        over = np.maximum(counts - -(-(len(s) + ahead) // n_rows), 0).sum()
        return broken + int(missing) + int(over)

    def check(self, kept: dict) -> dict:
        """Each number the check compares, with its limit."""
        limits = self.config["limits"]
        n_rows = self.config["data"]["rows"]
        ids, labels = kept["ids"].astype(np.int64), kept["labels"]
        want = ref.labels_by_id(self.data_dir)
        inside = (ids >= 0) & (ids < len(want))
        label_bad = int((~inside).sum()
                        + (want[ids[inside]] != labels[inside]).sum())
        stream = np.concatenate([ids, kept["drained"].astype(np.int64)])
        delivery_bad = self._delivery_errors(stream, len(ids))
        samples = list(kept["samples"].values())
        refs = ref.decode_ids(self.data_dir, {int(i) for s in samples
                                              for i in s["id"]
                                              if 0 <= i < n_rows})
        diffs, ulps = [], []
        for s in samples:
            # a row whose id is not in the dataset counts as all wrong
            blank = np.full(s["image"].shape[1:], -255, np.int16)
            want_px = np.stack([refs[int(i)] if int(i) in refs else blank
                                for i in s["id"]])
            diffs.append(np.abs(s["image"].astype(np.int16)
                                - want_px.astype(np.int16)))
            ulps.append(ref.bf16_ulps_off(s["norm"],
                                          ref.normalize(s["crop"])))
        diff = np.concatenate([d.ravel() for d in diffs])
        ulp = np.concatenate([u.ravel() for u in ulps])
        values = {
            "label_mismatches": label_bad,
            "delivery_errors": delivery_bad,
            "pixel_max_diff": int(diff.max()),
            "pixel_mean_diff": float(diff.mean()),
            # the tail a float32 IDCT leaves against libjpeg's integer one;
            # one bfloat16 pass widens it about twentyfold
            "pixels_3_off_per_million": 1e6 * float((diff >= 3).mean()),
            "normalize_ulp_errors": int((ulp > 1).sum()),
            "nonfinite_losses": int((~np.isfinite(kept["losses"])).sum()),
        }
        log(f"check: {len(ids)} rows delivered over"
            f" {len(kept['losses'])} steps, {len(kept['drained'])} pulled"
            f" after the window, {len(kept['samples'])} sampled batches")
        return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
