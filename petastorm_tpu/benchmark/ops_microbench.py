"""``python -m petastorm_tpu.benchmark.ops_microbench``: on-chip op timings.

Measures, on the real accelerator, three op-level claims: the Pallas
normalize kernel vs its XLA fallback vs host-side numpy, the flip+normalize
fusion, and the hybrid jpeg decode crossover vs host full
decode.  Prints one JSON line per measurement.
"""

from __future__ import annotations

import json
import time


def _timeit(fn, n=20):
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1000


def main() -> int:
    import jax
    import numpy as np

    from petastorm_tpu.ops import normalize as nmod
    from petastorm_tpu.ops.augment import random_flip
    from petastorm_tpu.ops.normalize import _choose_block, normalize_images

    B, H, W, C = 256, 224, 224, 3
    imgs_host = np.random.randint(0, 255, (B, H, W, C), dtype=np.uint8)
    imgs = jax.device_put(imgs_host)
    jax.block_until_ready(imgs)
    # normalize_images takes torchvision-style [0,1]-unit mean/std
    # (ops/normalize.py:91); the host baseline below computes the SAME
    # function so the timings compare like for like
    mean = np.array([0.485, 0.456, 0.406], np.float32)
    std = np.array([0.229, 0.224, 0.225], np.float32)

    # the SAME backend test normalize_images uses, so engagement reporting
    # cannot drift from what the op actually does
    on_tpu = jax.default_backend() == "tpu"
    pallas_engaged = bool(on_tpu and _choose_block(B, H * W * C) is not None)
    print(json.dumps({"metric": "pallas_engaged", "value": pallas_engaged,
                      "backend": jax.default_backend()}), flush=True)
    if on_tpu and not pallas_engaged:
        raise SystemExit(
            "on a TPU but the Pallas normalize path did not engage; the"
            " published kernel numbers cannot be reproduced")

    t_main = _timeit(lambda: normalize_images(imgs, mean, std))
    orig = nmod._choose_block
    nmod._choose_block = lambda n, length: None  # force the XLA fallback
    try:
        t_xla = _timeit(lambda: normalize_images(imgs, mean, std))
    finally:
        nmod._choose_block = orig

    def host_norm():
        return jax.device_put(
            (imgs_host.astype(np.float32) / 255.0 - mean) / std)

    t_host = _timeit(host_norm, n=5)
    print(json.dumps({"metric": "normalize_ms_per_256imgs",
                      "pallas" if pallas_engaged else "device": round(t_main, 3),
                      "xla_fallback": round(t_xla, 3),
                      "host_numpy_plus_f32_transfer": round(t_host, 1)}),
          flush=True)

    key = jax.random.PRNGKey(0)
    t_aug = _timeit(lambda: normalize_images(random_flip(imgs, key), mean, std))
    print(json.dumps({"metric": "flip_plus_normalize_ms_per_256imgs",
                      "value": round(t_aug, 3)}), flush=True)

    import itertools

    from petastorm_tpu.ops import random_resized_crop

    big = jax.device_put(np.random.randint(0, 255, (B, 256, 256, C),
                                           dtype=np.uint8))
    jax.block_until_ready(big)
    ctr = itertools.count()

    def _k():
        return jax.random.fold_in(key, next(ctr))

    t_rrc = _timeit(lambda: random_resized_crop(big, _k(), (224, 224)))
    t_rrc_aa = _timeit(lambda: random_resized_crop(
        big, _k(), (224, 224), antialias=True), n=5)
    t_full = _timeit(lambda: normalize_images(
        random_flip(random_resized_crop(big, _k(), (224, 224)), _k()),
        mean, std))
    print(json.dumps({"metric": "random_resized_crop_ms_per_256imgs_256to224",
                      "value": round(t_rrc, 3),
                      "antialiased": round(t_rrc_aa, 2),
                      "crop_flip_normalize_chain": round(t_full, 3)}),
          flush=True)

    try:
        import cv2
        import pyarrow as pa

        from petastorm_tpu.native.image import (available,
                                                decode_column_native,
                                                read_jpeg_coefficients_column)
        from petastorm_tpu.ops.jpeg import decode_coefficients
    except ImportError:
        return 0
    if not available():
        return 0

    from petastorm_tpu.test_util.synthetic import synthetic_jpeg_bytes

    bufs = synthetic_jpeg_bytes(64, H, W, quality=90)
    col = pa.array(bufs, type=pa.binary())
    out = np.empty((64, H, W, C), np.uint8)

    def host_path():
        decode_column_native(col, out, nthreads=1)
        return jax.device_put(out)

    planes, qtabs, layout = read_jpeg_coefficients_column(bufs)
    sampling = tuple((h, v) for (h, v, _, _) in layout.components)

    def hybrid_path():
        p, q, lay = read_jpeg_coefficients_column(bufs)
        jp, jq = jax.device_put((tuple(p), q))
        return decode_coefficients(jp, jq,
                                   image_size=(lay.height, lay.width),
                                   sampling=sampling)

    t_hostdec = _timeit(host_path, n=10)
    t_hyb = _timeit(hybrid_path, n=10)
    jp, jq = jax.device_put((tuple(planes), qtabs))
    t_chip = _timeit(lambda: decode_coefficients(
        jp, jq, image_size=(layout.height, layout.width), sampling=sampling),
        n=10)
    t0 = time.perf_counter()
    for _ in range(10):
        read_jpeg_coefficients_column(bufs)
    t_entropy = (time.perf_counter() - t0) / 10 * 1000
    print(json.dumps({"metric": "jpeg_decode_ms_per_64imgs_224",
                      "host_decode_plus_transfer": round(t_hostdec, 1),
                      "hybrid_total": round(t_hyb, 1),
                      "hybrid_host_entropy_half": round(t_entropy, 1),
                      "hybrid_chip_half": round(t_chip, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
