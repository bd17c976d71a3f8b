"""The ILSVRC cell: JPEGs at ILSVRC-2012's stored geometry, made from the
seed, read through ``make_reader(decode_placement={'image': 'device-mixed'})``
-> ``JaxDataLoader`` (the mix's ``pad_shapes`` canvases) into a step that
crops inside each image's extent.

A subclass of the image cells' pipeline: the delivery and label checks
are theirs.  This adds the geometry, the extents, the crop and their
checks.  The mix reaches the canvases through a list of ``pad_shapes``
targets for a 'device-mixed' field, which a loader without canvases
refuses when it is built, before any batch.
"""

from __future__ import annotations

import json
import os
import shutil
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from benchmarks.harness import key_seed, log, seed_rng, seed_words
from benchmarks.pipelines import images
from benchmarks.reference import ilsvrc as ref
from benchmarks.reference import images as ref_images

#: the dataset's measured geometry and size, written beside it
STATS = "_STATS.json"


def geometries(seed: int, data: dict) -> np.ndarray:
    """int ``(rows, 2)`` (height, width) of every row, from the seed and
    the configuration's ``geometry`` (see its ``assumed.geometry``)."""
    g, n = data["geometry"], data["rows"]
    rng = seed_rng(seed, 6)
    long = np.where(rng.random(n) < g["share_at_long_side"], g["long_side"],
                    rng.integers(g["long_min"], g["long_side"], n))
    tail = rng.random(n) < g["tail_share"]
    long = np.where(tail, rng.integers(g["long_side"] + 1, g["tail_max"] + 1,
                                       n), long)
    u = rng.random(n)
    aspect = rng.uniform(*g["aspect_range"], n)
    start = 0.0
    for a, share in g["aspects"]:
        aspect = np.where((u >= start) & (u < start + share), a, aspect)
        start += share
    short = np.maximum(np.round(long / aspect), 1).astype(np.int64)
    landscape = rng.random(n) < g["landscape"]
    return np.stack([np.where(landscape, short, long),
                     np.where(landscape, long, short)], axis=1)


def natural_image(seed: int, index: int, h: int, w: int, texture: dict):
    """One h x w image with natural-image structure (smooth regions, hard
    and soft edges, texture), made from (seed, index) alone: the image
    cells' generator on a rectangle."""
    import cv2

    rng = np.random.default_rng([index] + seed_words(seed))
    base = rng.uniform(0, 255, (4, 4, 3)).astype(np.float32)
    img = cv2.resize(base, (w, h), interpolation=cv2.INTER_CUBIC)
    side = min(h, w)
    lo, hi = texture["shapes"]
    for _ in range(int(rng.integers(lo, hi))):
        color = tuple(float(c) for c in rng.uniform(0, 255, 3))
        kind = int(rng.integers(0, 3))
        c = (int(rng.integers(0, w)), int(rng.integers(0, h)))
        if kind == 0:
            axes = tuple(int(v) for v in rng.integers(max(side // 16, 1),
                                                      max(side // 3, 2), 2))
            cv2.ellipse(img, c, axes, float(rng.uniform(0, 180)), 0, 360,
                        color, -1, cv2.LINE_AA)
        elif kind == 1:
            d = (int(rng.integers(0, w)), int(rng.integers(0, h)))
            cv2.rectangle(img, c, d, color, -1)
        else:
            k = int(rng.integers(3, 7))
            pts = np.stack([rng.integers(0, w, k), rng.integers(0, h, k)], 1)
            cv2.fillPoly(img, [pts.astype(np.int32)], color, cv2.LINE_AA)

    def noise(shape, sd):
        u = np.frombuffer(rng.bytes(int(np.prod(shape))), np.uint8)
        return (u.reshape(shape).astype(np.float32) - 127.5) * (sd / 73.9)

    img += cv2.resize(noise((max(h // 2, 1), max(w // 2, 1), 3),
                            texture["mid"]), (w, h),
                      interpolation=cv2.INTER_LINEAR)
    img += noise((h, w, 3), texture["fine"])
    return np.clip(img, 0, 255).astype(np.uint8)


def _encode_rows(seed: int, start: int, stop: int, data: dict):
    """JPEG bytes of rows [start, stop) at their geometry, as the
    program's ``CompressedImageCodec`` stores them (OpenCV, 4:2:0)."""
    import cv2

    cv2.setNumThreads(1)
    hw = geometries(seed, data)
    out = []
    for i in range(start, stop):
        img = natural_image(seed, i, int(hw[i, 0]), int(hw[i, 1]),
                            data["texture"])
        ok, enc = cv2.imencode(".jpeg", img[..., ::-1],
                               [int(cv2.IMWRITE_JPEG_QUALITY),
                                data["jpeg_quality"]])
        if not ok:
            raise RuntimeError(f"JPEG encode of row {i} failed")
        out.append(enc.tobytes())
    return out


def write_dataset(path: str, seed: int, data: dict) -> dict:
    """The dataset at ``path`` (ids 0..rows-1, labels and variable-size
    images from the seed, the geometry contract stamped); returns its
    measured geometry and size.  Rows are made in spawned processes, which
    never touch JAX."""
    import multiprocessing

    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import stamp_dataset_metadata
    from petastorm_tpu.schema import Field, Schema

    rows = data["rows"]
    schema = Schema("IlsvrcLike", [
        Field("id", np.int64, (), ScalarCodec()),
        Field("label", np.int64, (), ScalarCodec()),
        Field("image", np.uint8, (None, None, 3),
              CompressedImageCodec("jpeg", quality=data["jpeg_quality"])),
    ])
    labels = seed_rng(seed, 2).integers(0, data["num_classes"], rows)
    hw = geometries(seed, data)
    os.makedirs(path)
    total = 0
    chunk = 32
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
    stamp_dataset_metadata(path, schema, geometries={
        "image": {(int(h), int(w), 3) for h, w in hw}})
    return {"bytes_per_row": total / rows,
            "mean_height": float(hw[:, 0].mean()),
            "mean_width": float(hw[:, 1].mean()),
            "geometries": len({(int(h), int(w)) for h, w in hw})}


class Pipeline(images.Pipeline):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.extents = []
        self.stats = None

    # -- set-up ---------------------------------------------------------------

    def prepare(self) -> None:
        done = os.path.join(self.data_dir, images.DONE)
        if not os.path.exists(done):
            partial = self.data_dir + ".partial"
            shutil.rmtree(partial, ignore_errors=True)
            stats = write_dataset(partial, self.seed, self.config["data"])
            with open(os.path.join(partial, STATS), "w") as f:
                json.dump(stats, f)
            with open(os.path.join(partial, images.DONE), "w") as f:
                f.write(f"{stats['bytes_per_row']}\n")
            shutil.rmtree(self.data_dir, ignore_errors=True)
            os.replace(partial, self.data_dir)
        with open(os.path.join(self.data_dir, STATS)) as f:
            self.stats = json.load(f)
        s = self.stats
        log(f"dataset: {self.config['data']['rows']} rows,"
            f" {s['bytes_per_row']:.0f} bytes per row on average, mean"
            f" geometry {s['mean_width']:.1f}x{s['mean_height']:.1f}"
            f" (width x height), {s['geometries']} distinct geometries")

    def open(self) -> None:
        import jax
        from jax.sharding import PartitionSpec as P

        from petastorm_tpu.jax import JaxDataLoader
        from petastorm_tpu.reader import make_reader

        # the loader first: one without canvases refuses the mix here,
        # before the step compiles
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
        self.consumer = self.consumer_mod.Consumer(
            self.config, self.mesh, self.batch, key_seed(self.seed))
        self.key = jax.random.PRNGKey(key_seed(self.seed) + 1)

    # -- the timed path -------------------------------------------------------

    def step(self, batch, i):
        import jax

        loss, seen = self.consumer(batch["image"], batch["image_extent"],
                                   batch["label"],
                                   jax.random.fold_in(self.key, i))
        self.ids.append(batch["id"])
        self.labels.append(batch["label"])
        self.extents.append(batch["image_extent"])
        self.losses.append(loss)
        kept = {"id": batch["id"], "box": seen[0], "crop": seen[1],
                "flipped": seen[2], "norm": seen[3], "image": seen[4]}
        return loss, kept

    def shapes(self) -> dict:
        side = self.config["model"]["input_side"]
        s = self.stats or {}
        return {"rows": self.batch, "chips": self.mesh.size,
                "height": side, "width": side,
                "sampling": self.config["data"]["sampling"],
                "normalize_row_elems": side * side * 3,
                "mean_height": s.get("mean_height"),
                "mean_width": s.get("mean_width")}

    def programs(self) -> dict:
        return {"step": "jit_train_step", "finish": "jit_finish_canvas"}

    # -- after the window -----------------------------------------------------

    def fetch(self, samples) -> dict:
        extents = np.concatenate([np.asarray(a) for a in self.extents])
        self.extents = None
        out = super().fetch(samples)
        out["extents"] = extents
        return out

    def check(self, kept: dict) -> dict:
        """Each number the check compares, with its limit."""
        from benchmarks.consumers.resnet50 import check_rows
        from benchmarks.consumers.resnet50_ilsvrc import sample_rows

        limits = self.config["limits"]
        ids, labels = kept["ids"].astype(np.int64), kept["labels"]
        want = ref_images.labels_by_id(self.data_dir)
        n_rows = len(want)
        inside = (ids >= 0) & (ids < n_rows)
        label_bad = int((~inside).sum()
                        + (want[ids[inside]] != labels[inside]).sum())
        stream = np.concatenate([ids, kept["drained"].astype(np.int64)])
        delivery_bad = self._delivery_errors(stream, len(ids))
        geom = ref.geometry_by_id(self.data_dir)
        extent_bad = int((~inside).sum() + (
            geom[ids[inside]] != kept["extents"][inside]).any(axis=1).sum())

        samples = list(kept["samples"].values())
        rows = check_rows(self.batch)
        pixel_rows = sample_rows(self.batch)
        wanted = {int(i) for s in samples
                  for i in np.concatenate([s["id"][rows], s["id"][pixel_rows]])
                  if 0 <= i < n_rows}
        refs = ref_images.decode_ids(self.data_dir, wanted)
        side = self.config["model"]["input_side"]
        diffs, ulps = [], []
        box_bad = flip_bad = 0
        crop_max = 0
        for s in samples:
            for k, i in enumerate(s["id"][pixel_rows]):
                i = int(i)
                if i not in refs:   # not in the dataset: all wrong
                    diffs.append(np.full(1, 255, np.int16))
                    continue
                h, w = refs[i].shape[:2]
                diffs.append(np.abs(s["image"][k, :h, :w].astype(np.int16)
                                    - refs[i].astype(np.int16)).ravel())
            for k, i in enumerate(s["id"][rows]):
                i, box = int(i), s["box"][k]
                crop, flipped = s["crop"][k], s["flipped"][k]
                if (i not in refs or not ref.box_ok(box, *geom[i])):
                    box_bad += 1
                    crop_max = 255
                else:
                    got = crop.astype(np.int16)
                    crop_max = max(crop_max, int(np.abs(
                        got - ref.resample(refs[i], box, (side, side))).max()))
                if not (np.array_equal(flipped, crop)
                        or np.array_equal(flipped, crop[:, ::-1])):
                    flip_bad += 1
            ulps.append(ref_images.bf16_ulps_off(
                s["norm"], ref_images.normalize(s["flipped"])))
        diff = np.concatenate(diffs)
        ulp = np.concatenate([u.ravel() for u in ulps])
        values = {
            "label_mismatches": label_bad,
            "delivery_errors": delivery_bad,
            "extent_mismatches": extent_bad,
            "crop_box_errors": box_bad,
            "flip_errors": flip_bad,
            "pixel_max_diff": int(diff.max()),
            "pixel_mean_diff": float(diff.mean()),
            "pixels_3_off_per_million": 1e6 * float((diff >= 3).mean()),
            "crop_pixel_max_diff": crop_max,
            "normalize_ulp_errors": int((ulp > 1).sum()),
            "nonfinite_losses": int((~np.isfinite(kept["losses"])).sum()),
        }
        log(f"check: {len(ids)} rows delivered over"
            f" {len(kept['losses'])} steps, {len(kept['drained'])} pulled"
            f" after the window, {len(samples)} sampled batches,"
            f" {len(set(map(tuple, kept['extents'])))} distinct geometries"
            f" delivered")
        return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
