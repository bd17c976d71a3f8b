"""Plain reference for the ILSVRC cell: each stored JPEG's geometry (its
header, read by Pillow), the rules a random-resized-crop box keeps
(torchvision's ``RandomResizedCrop``), and the bilinear resample of a box
in float32 numpy, as PyTorch's ``interpolate`` resamples the cropped box.

The decode itself is ``reference/images.py``'s (Pillow).  Nothing here
imports the program or takes what it made.
"""

from __future__ import annotations

import io
from typing import Sequence, Tuple

import numpy as np
import pyarrow.parquet as pq
from PIL import Image

from benchmarks.reference.images import _files

SCALE = (0.08, 1.0)
RATIO = (3.0 / 4.0, 4.0 / 3.0)


def geometry_by_id(url: str) -> np.ndarray:
    """int64 ``(N, 2)``: the (height, width) each stored JPEG's header
    gives, by id (ids are 0..N-1)."""
    ids, hw = [], []
    for f in _files(url):
        t = pq.read_table(f, columns=["id", "image"])
        ids.append(t.column("id").to_numpy())
        for blob in t.column("image").to_pylist():
            w, h = Image.open(io.BytesIO(blob)).size
            hw.append((h, w))
    ids = np.concatenate(ids)
    out = np.full((int(ids.max()) + 1, 2), -1, np.int64)
    out[ids] = np.asarray(hw, np.int64)
    return out


def box_ok(box: Sequence[int], h: int, w: int,
           scale: Tuple[float, float] = SCALE,
           ratio: Tuple[float, float] = RATIO) -> bool:
    """``(top, left, height, width)`` lies inside an image of (h, w), and
    is either a torchvision candidate (an area in ``scale`` of the image's
    and an aspect width / height in ``ratio``, up to the rounding of its
    sides to whole pixels) or torchvision's centre fallback."""
    top, left, bh, bw = (int(v) for v in box)
    if not (bh >= 1 and bw >= 1 and top >= 0 and left >= 0
            and top + bh <= h and left + bw <= w):
        return False
    area_ok = ((bh - 0.5) * (bw - 0.5) <= scale[1] * h * w
               and (bh + 0.5) * (bw + 0.5) >= scale[0] * h * w)
    ratio_ok = ((bw - 0.5) / (bh + 0.5) <= ratio[1]
                and (bw + 0.5) / (bh - 0.5) >= ratio[0])
    in_ratio = w / h
    fw = int(round(h * ratio[1])) if in_ratio > ratio[1] else w
    fh = int(round(w / ratio[0])) if in_ratio < ratio[0] else h
    fallback = (bh, bw, top, left) == (fh, fw, (h - fh) // 2, (w - fw) // 2)
    return (area_ok and ratio_ok) or fallback


def _taps(size: int, n: int):
    """Source taps of ``n`` outputs over ``size`` inputs: half-pixel
    centres, the scale as size times the float32 reciprocal of n, clamped
    at 0 and at the last input."""
    src = (np.float32(size) * np.float32(1.0 / n)
           * (np.arange(n, dtype=np.float32) + np.float32(0.5))
           - np.float32(0.5))
    src = np.maximum(src, np.float32(0.0))
    i0 = src.astype(np.int32)
    return i0, np.minimum(i0 + 1, size - 1), src - i0.astype(np.float32)


def resample(image: np.ndarray, box: Sequence[int],
             out_hw: Tuple[int, int]) -> np.ndarray:
    """uint8 ``out_hw`` crop of ``image[top:top+h, left:left+w]``: bilinear
    in float32, rows then columns, rounded half to even."""
    top, left, bh, bw = (int(v) for v in box)
    crop = image[top:top + bh, left:left + bw].astype(np.float32)
    y0, y1, ly = _taps(bh, out_hw[0])
    x0, x1, lx = _taps(bw, out_hw[1])
    ly, lx = ly[:, None, None], lx[None, :, None]
    rows = crop[y0] * (np.float32(1.0) - ly) + crop[y1] * ly
    out = rows[:, x0] * (np.float32(1.0) - lx) + rows[:, x1] * lx
    return np.clip(np.round(out), 0, 255).astype(np.uint8)
