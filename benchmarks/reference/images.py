"""Plain reference for the image cells: the dataset's own rows, read with
pyarrow and decoded with Pillow (libjpeg's default decode: integer IDCT,
fancy chroma upsampling), and the normalize as its plain expression.

Nothing here imports the program or takes what it made: the rows come
from the Parquet files on disk, and the normalize constants are the
torchvision ones the consumer states.
"""

from __future__ import annotations

import glob
import io
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List

import ml_dtypes
import numpy as np
import pyarrow.parquet as pq
from PIL import Image

MEAN = np.asarray([0.485, 0.456, 0.406], np.float64)
STD = np.asarray([0.229, 0.224, 0.225], np.float64)


def _files(url: str):
    return sorted(glob.glob(os.path.join(url, "**", "*.parquet"),
                            recursive=True))


def labels_by_id(url: str) -> np.ndarray:
    """``label[id]`` over the whole dataset (ids are 0..N-1)."""
    ids, labels = [], []
    for f in _files(url):
        t = pq.read_table(f, columns=["id", "label"])
        ids.append(t.column("id").to_numpy())
        labels.append(t.column("label").to_numpy())
    ids = np.concatenate(ids)
    out = np.full(int(ids.max()) + 1, -1, np.int64)
    out[ids] = np.concatenate(labels)
    return out


def row_groups(url: str) -> List[np.ndarray]:
    """The ids of each row group, in stored order: a reader delivers a
    row group whole and in this order."""
    out = []
    for f in _files(url):
        pf = pq.ParquetFile(f)
        for g in range(pf.num_row_groups):
            out.append(pf.read_row_group(g, columns=["id"])
                       .column("id").to_numpy())
    return out


def jpeg_bytes(url: str, wanted: Iterable[int]) -> Dict[int, bytes]:
    """The stored JPEG of each wanted id."""
    wanted = set(int(i) for i in wanted)
    found: Dict[int, bytes] = {}
    for f in _files(url):
        ids = pq.read_table(f, columns=["id"]).column("id").to_numpy()
        rows = [k for k, i in enumerate(ids) if int(i) in wanted]
        if not rows:
            continue
        col = pq.read_table(f, columns=["image"]).column("image")
        for k in rows:
            found[int(ids[k])] = col[k].as_py()
    missing = wanted - set(found)
    if missing:
        raise KeyError(f"ids not in the dataset: {sorted(missing)[:10]}")
    return found


def decode(data: bytes) -> np.ndarray:
    return np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))


def decode_ids(url: str, ids: Iterable[int]) -> Dict[int, np.ndarray]:
    blobs = jpeg_bytes(url, ids)
    keys = sorted(blobs)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        images = list(ex.map(decode, (blobs[k] for k in keys)))
    return dict(zip(keys, images))


def normalize(pixels_u8: np.ndarray) -> np.ndarray:
    """``(u8 / 255 - mean) / std`` in float64, rounded to bfloat16."""
    x = (pixels_u8.astype(np.float64) / 255.0 - MEAN) / STD
    return x.astype(ml_dtypes.bfloat16)


def bf16_ulps_off(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """How many bfloat16 steps ``got`` lies from ``want``, element-wise."""
    g = np.asarray(got, ml_dtypes.bfloat16).view(np.uint16).astype(np.int32)
    w = np.asarray(want, ml_dtypes.bfloat16).view(np.uint16).astype(np.int32)
    # sign-magnitude bit patterns to a monotone integer line
    g = np.where(g & 0x8000, -(g & 0x7FFF), g)
    w = np.where(w & 0x8000, -(w & 0x7FFF), w)
    return np.abs(g - w)
