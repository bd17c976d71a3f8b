"""On-device batched image augmentation: random crop + horizontal flip.

The standard ImageNet training transforms, run ON-CHIP after delivery (or
after the hybrid jpeg decode) instead of on host workers: uint8 in, uint8
out, fully batched under ``jit`` with per-image randomness derived from one
key.  Host workers stay decode-only, the augmentation costs no host CPU and
no extra host->device bytes, and XLA fuses the gather/flip into whatever
follows (normalize, first conv).

Reference analog: none - the reference leaves augmentation to the consumer
framework (torchvision/tf.image on host).  Keeping it device-side is the
TPU-first translation of that stage.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("crop_hw",))
def random_crop(images: jax.Array, key: jax.Array,
                crop_hw: Tuple[int, int]) -> jax.Array:
    """Per-image random crop of an (N, H, W, C) batch to (N, ch, cw, C)."""
    n, h, w, _ = images.shape
    ch, cw = crop_hw
    if ch > h or cw > w:
        raise ValueError(f"crop {crop_hw} larger than image {(h, w)}")
    ky, kx = jax.random.split(key)
    ys = jax.random.randint(ky, (n,), 0, h - ch + 1)
    xs = jax.random.randint(kx, (n,), 0, w - cw + 1)

    def crop_one(img, y, x):
        return jax.lax.dynamic_slice(img, (y, x, 0),
                                     (ch, cw, img.shape[-1]))

    return jax.vmap(crop_one)(images, ys, xs)


@jax.jit
def random_flip(images: jax.Array, key: jax.Array) -> jax.Array:
    """Per-image horizontal flip with probability 0.5, (N, H, W, C)."""
    flip = jax.random.bernoulli(key, 0.5, (images.shape[0],))
    return jnp.where(flip[:, None, None, None], images[:, :, ::-1, :], images)


@functools.partial(jax.jit, static_argnames=("crop_hw",))
def random_crop_flip(images: jax.Array, key: jax.Array,
                     crop_hw: Optional[Tuple[int, int]] = None) -> jax.Array:
    """Crop (when ``crop_hw`` is set) then flip - the ImageNet train pair."""
    k1, k2 = jax.random.split(key)
    if crop_hw is not None:
        images = random_crop(images, k1, crop_hw)
    return random_flip(images, k2)


@functools.partial(jax.jit, static_argnames=("alpha",))
def mixup(images: jax.Array, labels: jax.Array, key: jax.Array,
          alpha: float = 0.2):
    """Batch mixup (Zhang et al. 2017), on-chip: blend each image with a
    permuted partner using one Beta(alpha, alpha) lambda per batch.

    Returns ``(mixed_images, labels, permuted_labels, lam)``; compute the
    loss as ``lam * ce(logits, labels) + (1 - lam) * ce(logits,
    permuted_labels)``.  uint8 images mix in float32 and come back uint8;
    float images keep their dtype.
    """
    k_lam, k_perm = jax.random.split(key)
    lam = jax.random.beta(k_lam, alpha, alpha)
    lam = jnp.maximum(lam, 1.0 - lam)  # keep the dominant image first
    perm = jax.random.permutation(k_perm, images.shape[0])
    x = images.astype(jnp.float32)
    mixed = lam * x + (1.0 - lam) * x[perm]
    return _restore_dtype(mixed, images.dtype), labels, labels[perm], lam


@functools.partial(jax.jit, static_argnames=("alpha",))
def cutmix(images: jax.Array, labels: jax.Array, key: jax.Array,
           alpha: float = 1.0):
    """Batch CutMix (Yun et al. 2019), on-chip: paste one random box from a
    permuted partner into every image; one box per batch (the paper's
    formulation), so the patch becomes a static-shape masked blend.

    Returns ``(mixed_images, labels, permuted_labels, lam)`` with ``lam``
    the kept-area fraction, recomputed from the actual box.  Dtype is
    preserved exactly (pure selection, no resampling).
    """
    n, h, w, _ = images.shape
    k_lam, k_perm, k_y, k_x = jax.random.split(key, 4)
    lam0 = jax.random.beta(k_lam, alpha, alpha)
    cut = jnp.sqrt(1.0 - lam0)
    bh = (cut * h).astype(jnp.int32)
    bw = (cut * w).astype(jnp.int32)
    cy = jax.random.randint(k_y, (), 0, h)
    cx = jax.random.randint(k_x, (), 0, w)
    y0 = jnp.clip(cy - bh // 2, 0, h)
    y1 = jnp.clip(cy + bh // 2, 0, h)
    x0 = jnp.clip(cx - bw // 2, 0, w)
    x1 = jnp.clip(cx + bw // 2, 0, w)
    rows = jnp.arange(h)[None, :, None, None]
    cols = jnp.arange(w)[None, None, :, None]
    in_box = ((rows >= y0) & (rows < y1) & (cols >= x0) & (cols < x1))
    perm = jax.random.permutation(k_perm, n)
    mixed = jnp.where(in_box, images[perm], images)
    lam = 1.0 - ((y1 - y0) * (x1 - x0)) / (h * w)
    return mixed, labels, labels[perm], lam


def _restore_dtype(out: jax.Array, src_dtype) -> jax.Array:
    """float32 resample result -> the source dtype (round+clip for ints)."""
    if jnp.issubdtype(src_dtype, jnp.integer):
        info = jnp.iinfo(src_dtype)
        return jnp.clip(jnp.round(out), info.min, info.max).astype(src_dtype)
    return out.astype(src_dtype)


@functools.partial(jax.jit, static_argnames=("out_hw", "method", "antialias"))
def resize_images(images: jax.Array, out_hw: Tuple[int, int],
                  method: str = "bilinear", antialias: bool = True) -> jax.Array:
    """Batched on-chip resize of (N, H, W, C) to (N, oh, ow, C).

    Antialiased by default (``jax.image.resize`` semantics) - the scale here
    is STATIC, so XLA specializes the filter support and the cost stays
    small; uint8 inputs round-trip through float32 and come back uint8,
    float inputs keep their dtype.
    """
    n, _, _, c = images.shape
    oh, ow = out_hw
    x = images.astype(jnp.float32)
    out = jax.image.resize(x, (n, oh, ow, c), method=method,
                           antialias=antialias)
    return _restore_dtype(out, images.dtype)


#: candidate boxes ``crop_boxes`` draws per image before it falls back to
#: the centre crop (torchvision's RandomResizedCrop tries 10)
_CROP_TRIES = 10


@functools.partial(jax.jit, static_argnames=("scale", "ratio"))
def crop_boxes(key: jax.Array, extents: jax.Array,
               scale: Tuple[float, float] = (0.08, 1.0),
               ratio: Tuple[float, float] = (3.0 / 4.0, 4.0 / 3.0)
               ) -> jax.Array:
    """torchvision's ``RandomResizedCrop.get_params``, per image and
    branch-free: int32 ``(N, 4)`` boxes ``(top, left, height, width)``
    inside each image's own ``extents`` (int ``(N, 2)``: height, width).

    Ten candidate boxes per image, each of an area drawn in ``scale`` of
    the image's and an aspect (width / height) log-uniform in ``ratio``,
    sides rounded to whole pixels; the first that fits is placed uniformly
    at random.  When none fits, the centre crop with the aspect clamped to
    ``ratio``.  ``random_resized_crop(..., extents=...)`` crops these boxes
    with the same ``key``."""
    n = extents.shape[0]
    h = extents[:, 0].astype(jnp.float32)
    w = extents[:, 1].astype(jnp.float32)
    k_area, k_ratio, k_y, k_x = jax.random.split(key, 4)
    area = (h * w)[:, None] * jax.random.uniform(
        k_area, (n, _CROP_TRIES), minval=scale[0], maxval=scale[1])
    aspect = jnp.exp(jax.random.uniform(
        k_ratio, (n, _CROP_TRIES), minval=jnp.log(ratio[0]),
        maxval=jnp.log(ratio[1])))
    cw = jnp.round(jnp.sqrt(area * aspect))
    ch = jnp.round(jnp.sqrt(area / aspect))
    fits = (cw > 0) & (cw <= w[:, None]) & (ch > 0) & (ch <= h[:, None])
    first = jnp.argmax(fits, axis=1)[:, None]
    found = fits.any(axis=1)
    cw = jnp.take_along_axis(cw, first, axis=1)[:, 0]
    ch = jnp.take_along_axis(ch, first, axis=1)[:, 0]
    in_ratio = w / h
    fw = jnp.where(in_ratio > ratio[1], jnp.round(h * ratio[1]), w)
    fh = jnp.where(in_ratio < ratio[0], jnp.round(w / ratio[0]), h)
    bw = jnp.where(found, cw, fw).astype(jnp.int32)
    bh = jnp.where(found, ch, fh).astype(jnp.int32)
    hi, wi = extents[:, 0].astype(jnp.int32), extents[:, 1].astype(jnp.int32)
    top = jnp.where(found, jax.random.randint(k_y, (n,), 0, hi - bh + 1),
                    (hi - bh) // 2)
    left = jnp.where(found, jax.random.randint(k_x, (n,), 0, wi - bw + 1),
                     (wi - bw) // 2)
    return jnp.stack([top, left, bh, bw], axis=1)


def _taps(start: jax.Array, size: jax.Array, out_len: int):
    """Bilinear source taps of ``out_len`` output samples over each box
    ``[start, start + size)`` (``(N,)`` each), as PyTorch's bilinear
    ``interpolate`` (half-pixel centres, no antialias) takes them over the
    cropped box: ``(i0, i1, weight of i1)``, each ``(N, out_len)``, with
    both taps clamped to the box, so no sample reads outside it."""
    size = jnp.maximum(size, 1)
    # the scale as size times the float32 reciprocal, so that the compiler
    # has no division by a constant left to rewrite: a plain float32
    # reference then takes the very same taps
    scale = size.astype(jnp.float32) * np.float32(1.0 / out_len)
    centres = np.arange(out_len, dtype=np.float32) + np.float32(0.5)
    src = jnp.maximum(scale[:, None] * centres[None, :] - np.float32(0.5),
                      0.0)
    i0 = src.astype(jnp.int32)
    lam = src - i0.astype(jnp.float32)
    i1 = jnp.minimum(i0 + 1, size[:, None] - 1)
    return start[:, None] + i0, start[:, None] + i1, lam


def _crop_in_extents(images: jax.Array, boxes: jax.Array,
                     out_hw: Tuple[int, int]) -> jax.Array:
    """Bilinear resample of each ``(top, left, height, width)`` box of
    ``images`` to ``out_hw``, in float32: rows first, then columns."""
    oh, ow = out_hw
    y0, y1, ly = _taps(boxes[:, 0], boxes[:, 2], oh)
    x0, x1, lx = _taps(boxes[:, 1], boxes[:, 3], ow)

    def rows(idx):
        return jnp.take_along_axis(images, idx[:, :, None, None],
                                   axis=1).astype(jnp.float32)

    ly = ly[:, :, None, None]
    r = rows(y0) * (1.0 - ly) + rows(y1) * ly

    def cols(idx):
        return jnp.take_along_axis(r, idx[:, None, :, None], axis=2)

    lx = lx[:, None, :, None]
    return cols(x0) * (1.0 - lx) + cols(x1) * lx


@functools.partial(jax.jit,
                   static_argnames=("out_hw", "scale", "ratio", "method",
                                    "antialias"))
def random_resized_crop(images: jax.Array, key: jax.Array,
                        out_hw: Tuple[int, int],
                        scale: Tuple[float, float] = (0.08, 1.0),
                        ratio: Tuple[float, float] = (3.0 / 4.0, 4.0 / 3.0),
                        method: str = "bilinear",
                        antialias: bool = False,
                        extents: Optional[jax.Array] = None) -> jax.Array:
    """torchvision-style RandomResizedCrop, fully on-chip and batched.

    With ``extents`` (int ``(N, 2)``: each image's own height and width,
    as a 'device-mixed' loader delivers them beside a batch of canvases),
    each box is drawn inside its image's extent exactly as torchvision
    draws it (``crop_boxes``, same ``key``) and resampled bilinearly as
    torchvision's tensor ``resized_crop`` does without antialias, with
    every tap clamped to the box: nothing outside the image is read.
    Only ``method='bilinear'`` without ``antialias`` is offered there.

    Per image: sample a crop area fraction in ``scale`` and an aspect ratio
    log-uniform in ``ratio``, place the crop uniformly, and resize it to
    ``out_hw``.  Crop geometry varies per image but every shape is STATIC:
    the variable box becomes per-image scale/translation scalars fed to
    ``jax.lax`` scale-and-translate under ``vmap``, so XLA compiles one
    kernel for the whole batch (no dynamic shapes, no host round-trip).
    uint8 in -> uint8 out.

    ``antialias`` defaults OFF - plain bilinear sampling is the classic
    ImageNet-training behavior (torchvision pre-v2).  For this op's
    per-image traced scales the antialiased form costs about as much as the
    plain one, so turning it on for torchvision-v2 quality parity is fine.
    Beware hand-rolled variants whose crop scale constant-folds at trace
    time: they compile to a far slower kernel for the same batch - keep the
    scale a traced value if you fork this.
    """
    if extents is not None:
        if method != "bilinear" or antialias:
            raise ValueError("random_resized_crop with extents resamples"
                             " bilinearly without antialias only")
        boxes = crop_boxes(key, extents, scale, ratio)
        return _restore_dtype(_crop_in_extents(images, boxes, out_hw),
                              images.dtype)
    n, h, w, c = images.shape
    oh, ow = out_hw
    k_area, k_ratio, k_y, k_x = jax.random.split(key, 4)
    area_frac = jax.random.uniform(k_area, (n,), minval=scale[0],
                                   maxval=scale[1])
    log_r = jax.random.uniform(k_ratio, (n,),
                               minval=jnp.log(ratio[0]),
                               maxval=jnp.log(ratio[1]))
    r = jnp.exp(log_r)
    area = area_frac * (h * w)
    crop_w = jnp.sqrt(area * r)
    crop_h = jnp.sqrt(area / r)
    # clamp to the image (torchvision retries then falls back to center;
    # clamping keeps everything branch-free and on-chip)
    crop_w = jnp.clip(crop_w, 1.0, float(w))
    crop_h = jnp.clip(crop_h, 1.0, float(h))
    y0 = jax.random.uniform(k_y, (n,)) * (h - crop_h)
    x0 = jax.random.uniform(k_x, (n,)) * (w - crop_w)

    src_dtype = images.dtype
    x = images.astype(jnp.float32)

    def one(img, ch, cw, yy, xx):
        # map the crop box onto the (oh, ow) output grid: out = scale*in + t,
        # with translation chosen so in-coordinate y0 lands at out 0
        sy = oh / ch
        sx = ow / cw
        return jax.image.scale_and_translate(
            img, (oh, ow, c), (0, 1),
            jnp.stack([sy, sx]),
            jnp.stack([-yy * sy, -xx * sx]),
            method=method, antialias=antialias)

    out = jax.vmap(one)(x, crop_h, crop_w, y0, x0)
    return _restore_dtype(out, src_dtype)
