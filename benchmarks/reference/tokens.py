"""Plain reference for the token cells: the corpus read with pyarrow in
file and row-group order, packed by a straightforward first-fit packer.

The packing rule is the one the program documents for its packer (up to
``open_bins`` open rows; a document goes to the first open row it fits;
when none fits and all are open, the fullest row ships, the oldest on
ties; a document longer than ``seq_len`` is cut into ``seq_len`` chunks,
each its own segment).  Nothing here imports the program.
"""

from __future__ import annotations

import glob
import os
from typing import Iterator, List

import numpy as np
import pyarrow.parquet as pq


def documents(url: str) -> List[np.ndarray]:
    docs: List[np.ndarray] = []
    for f in sorted(glob.glob(os.path.join(url, "**", "*.parquet"),
                              recursive=True)):
        pf = pq.ParquetFile(f)
        for g in range(pf.num_row_groups):
            col = pf.read_row_group(g, columns=["tokens"]).column("tokens")
            for chunk in col.chunks:
                offsets = chunk.offsets.to_numpy()
                values = chunk.values.to_numpy()
                docs.extend(values[offsets[k]:offsets[k + 1]]
                            for k in range(len(chunk)))
    return docs


def packed_rows(docs: List[np.ndarray], seq_len: int, open_bins: int,
                n_rows: int) -> dict:
    """The first ``n_rows`` packed rows of ``docs`` read epoch after epoch:
    ``tokens``, ``segment_ids``, ``positions``, ``loss_mask``."""
    out = {k: np.zeros((n_rows, seq_len), np.int32)
           for k in ("tokens", "segment_ids", "positions")}
    out["loss_mask"] = np.zeros((n_rows, seq_len), np.float32)
    bins: List[list] = []          # [used, [chunks]] in creation order
    done = 0

    def emit(k: int) -> None:
        nonlocal done
        used, chunks = bins.pop(k)
        if done < n_rows:
            at = 0
            for seg, c in enumerate(chunks, start=1):
                out["tokens"][done, at:at + len(c)] = c
                out["segment_ids"][done, at:at + len(c)] = seg
                out["positions"][done, at:at + len(c)] = np.arange(len(c))
                out["loss_mask"][done, at:at + len(c)] = 1.0
                at += len(c)
        done += 1

    def chunks_of(doc) -> Iterator[np.ndarray]:
        for i in range(0, len(doc), seq_len):
            yield doc[i:i + seq_len]

    while done < n_rows:
        if not docs:
            raise ValueError("empty corpus")
        for doc in docs:
            if done >= n_rows:
                break
            for c in chunks_of(doc):
                for k, b in enumerate(bins):
                    if seq_len - b[0] >= len(c):
                        b[0] += len(c)
                        b[1].append(c)
                        if b[0] == seq_len:
                            emit(k)
                        break
                else:
                    if len(bins) >= open_bins:
                        emit(min(range(len(bins)),
                                 key=lambda k: seq_len - bins[k][0]))
                    bins.append([len(c), [c]])
                    if len(c) == seq_len:
                        emit(len(bins) - 1)
    return out
