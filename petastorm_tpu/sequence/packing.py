"""Deterministic sequence packing: variable-length documents -> fixed
``(batch, seq_len)`` token blocks.

The dominant production workload is token streams (ROADMAP item 4), and the
input-pipeline papers (tf.data, PAPERS.md) put packing/filtering *inside*
the input pipeline as first-class transformations.  This module is the
packing half: a streaming first-fit bin packer that turns a plan-ordered
stream of variable-length token documents into dense fixed-shape blocks
carrying document-boundary segment IDs, per-document positions and a
loss mask - the exact quadruple a packed-attention training step consumes.

Determinism is the design constraint, not an afterthought: the packer is a
pure function of the *document stream order* (no clocks, no RNG, no
arrival-time coupling), so under ``deterministic='seed'`` reader delivery
the packed stream is bit-identical across worker counts, executor flavors,
chaos kills and the service hop - and the chaos matrix certifies it
(tests/test_determinism_matrix.py token-dataset cells, via
:func:`packed_stream_digest`).

Two delivery modes:

* **packed** (:class:`SequencePacker` / :func:`iter_packed_blocks`): dense
  ``(batch, seq_len)`` blocks; fill-rate typically >= 0.85 on real-corpus
  length distributions.
* **ragged** (:func:`iter_ragged_batches`): flat token buffer + offsets for
  consumers that pack on-device (e.g. a jit-compiled packer kernel).

docs/operations.md "Token pipelines" is the operator-facing runbook.
"""

from __future__ import annotations

import struct
import zlib
from typing import Dict, Iterable, Iterator, List, Optional

import numpy as np

from petastorm_tpu.errors import PetastormTpuError

#: long-document policies (documents longer than ``seq_len``)
LONG_DOC_POLICIES = ("split", "truncate", "error")


class _Bin:
    """One open packing bin: preallocated output row being filled."""

    __slots__ = ("tokens", "segment_ids", "positions", "loss_mask", "used",
                 "segments")

    def __init__(self, seq_len: int, tokens_dtype, mask_dtype, pad_token):
        self.tokens = np.full(seq_len, pad_token, dtype=tokens_dtype)
        self.segment_ids = np.zeros(seq_len, dtype=np.int32)
        self.positions = np.zeros(seq_len, dtype=np.int32)
        self.loss_mask = np.zeros(seq_len, dtype=mask_dtype)
        self.used = 0
        self.segments = 0

    def place(self, doc: np.ndarray) -> None:
        n = len(doc)
        lo, hi = self.used, self.used + n
        self.tokens[lo:hi] = doc
        self.segments += 1
        self.segment_ids[lo:hi] = self.segments
        self.positions[lo:hi] = np.arange(n, dtype=np.int32)
        self.loss_mask[lo:hi] = 1
        self.used = hi

    def row(self) -> Dict[str, np.ndarray]:
        return {"tokens": self.tokens, "segment_ids": self.segment_ids,
                "positions": self.positions, "loss_mask": self.loss_mask}


class SequencePacker:
    """Streaming first-fit-shrinking bin packer over a document stream.

    Feed variable-length 1-D token arrays in stream order; completed rows
    come back as ``{'tokens', 'segment_ids', 'positions', 'loss_mask'}``
    dicts of ``(seq_len,)`` arrays.  ``segment_ids`` are 1-based per packed
    document (0 = padding), ``positions`` restart at 0 per document, and
    ``loss_mask`` is 1 on real tokens, 0 on padding - the standard packed
    segment-attention contract.

    The algorithm (exactly this, because the packed stream must be a pure
    function of document order - the chaos matrix certifies it):

    * up to ``open_bins`` partially-filled bins are kept, in creation order;
    * each document goes to the FIRST open bin it fits (first-fit); a bin
      that fills exactly closes and is emitted immediately;
    * when nothing fits and the open set is full, the bin with the LEAST
      remaining capacity (oldest on ties) closes and is emitted - bins
      shrink until they cannot absorb the incoming document, then the most-
      shrunk one ships - and a fresh bin takes the document;
    * ``finish()`` emits the remaining bins in creation order.

    Documents longer than ``seq_len`` follow ``long_docs``: ``'split'``
    (default; chunks of ``seq_len``, each chunk packs as its own segment),
    ``'truncate'`` (keep the first ``seq_len`` tokens) or ``'error'``.
    Empty (and None) documents are skipped and counted.

    ``stats()`` reports fill-rate and token/document accounting; with a
    ``telemetry`` recorder the same numbers ride the ``sequence.*`` series
    (docs/operations.md "Token pipelines").
    """

    def __init__(self, seq_len: int, open_bins: int = 8,
                 long_docs: str = "split", tokens_dtype=np.int32,
                 mask_dtype=np.float32, pad_token: int = 0,
                 telemetry=None):
        if seq_len < 1:
            raise PetastormTpuError("seq_len must be >= 1")
        if open_bins < 1:
            raise PetastormTpuError("open_bins must be >= 1")
        if long_docs not in LONG_DOC_POLICIES:
            raise PetastormTpuError(
                f"long_docs must be one of {LONG_DOC_POLICIES}; got"
                f" {long_docs!r}")
        self.seq_len = int(seq_len)
        self._open_limit = int(open_bins)
        self._long_docs = long_docs
        self._tokens_dtype = np.dtype(tokens_dtype)
        self._mask_dtype = np.dtype(mask_dtype)
        self._pad_token = pad_token
        self._bins: List[_Bin] = []
        self._finished = False
        # accounting (stats() / sequence.* telemetry)
        self._docs = 0
        self._docs_split = 0
        self._docs_truncated = 0
        self._docs_empty = 0
        self._tokens = 0
        self._rows = 0
        from petastorm_tpu.telemetry import resolve as _resolve_telemetry

        self._telemetry = _resolve_telemetry(telemetry)
        tele = self._telemetry
        self._m_docs = tele.counter("sequence.docs_packed")
        self._m_tokens = tele.counter("sequence.tokens_packed")
        self._m_pad = tele.counter("sequence.pad_tokens")
        self._m_rows = tele.counter("sequence.rows_emitted")
        self._m_split = tele.counter("sequence.docs_split")
        self._g_fill = tele.gauge("sequence.fill_rate")

    def _emit(self, idx: int) -> Dict[str, np.ndarray]:
        b = self._bins.pop(idx)
        self._rows += 1
        if self._telemetry.enabled:
            self._m_rows.add(1)
            self._m_pad.add(self.seq_len - b.used)
            self._g_fill.set(self.fill_rate)
        return b.row()

    def feed(self, doc) -> List[Dict[str, np.ndarray]]:
        """Pack one document; returns the rows this document completed
        (usually none or one - more when a long document splits).  With
        telemetry on, each call is a ``pack`` stage whose ``row`` is the
        number of the next row to be emitted: the packer's own work, the
        wait for the next document falling between the spans."""
        if self._telemetry.enabled:
            with self._telemetry.stage("pack", row=self._rows):
                return self._feed(doc)
        return self._feed(doc)

    def _feed(self, doc) -> List[Dict[str, np.ndarray]]:
        if self._finished:
            raise PetastormTpuError("SequencePacker.feed after finish()")
        if doc is None:
            self._docs_empty += 1
            return []
        doc = np.asarray(doc)
        if doc.ndim != 1:
            raise PetastormTpuError(
                f"documents must be 1-D token arrays; got shape {doc.shape}")
        if len(doc) == 0:
            self._docs_empty += 1
            return []
        self._docs += 1
        chunks: Iterable[np.ndarray]
        kept = len(doc)  # tokens this doc will actually emit
        if len(doc) <= self.seq_len:
            chunks = (doc,)
        elif self._long_docs == "split":
            self._docs_split += 1
            if self._telemetry.enabled:
                self._m_split.add(1)
            chunks = (doc[i:i + self.seq_len]
                      for i in range(0, len(doc), self.seq_len))
        elif self._long_docs == "truncate":
            self._docs_truncated += 1
            kept = self.seq_len
            chunks = (doc[:self.seq_len],)
        else:
            raise PetastormTpuError(
                f"document of {len(doc)} tokens exceeds seq_len"
                f" {self.seq_len} (long_docs='error')")
        self._tokens += kept
        if self._telemetry.enabled:
            # counted once, post-policy: Counters are monotonic (a negative
            # truncation correction would read as a reset to rate())
            self._m_docs.add(1)
            self._m_tokens.add(kept)
        out: List[Dict[str, np.ndarray]] = []
        for chunk in chunks:
            n = len(chunk)
            placed = False
            for i, b in enumerate(self._bins):
                if self.seq_len - b.used >= n:
                    b.place(chunk)
                    if b.used == self.seq_len:
                        out.append(self._emit(i))
                    placed = True
                    break
            if placed:
                continue
            if len(self._bins) >= self._open_limit:
                # evict the most-shrunk bin (least remaining; oldest on ties)
                out.append(self._emit(
                    min(range(len(self._bins)),
                        key=lambda i: self.seq_len - self._bins[i].used)))
            b = _Bin(self.seq_len, self._tokens_dtype, self._mask_dtype,
                     self._pad_token)
            b.place(chunk)
            self._bins.append(b)
            if b.used == self.seq_len:
                out.append(self._emit(len(self._bins) - 1))
        return out

    def finish(self) -> List[Dict[str, np.ndarray]]:
        """Close and emit the remaining open bins, in creation order."""
        self._finished = True
        out = []
        while self._bins:
            out.append(self._emit(0))
        return out

    @property
    def fill_rate(self) -> float:
        """Real tokens / emitted slots (0.0 before the first emitted row).
        Open-bin tokens are excluded until their bin emits."""
        slots = self._rows * self.seq_len
        if not slots:
            return 0.0
        pending = sum(b.used for b in self._bins)
        return (self._tokens - pending) / slots

    def stats(self) -> Dict:
        """Packing accounting: documents, tokens, emitted rows, fill rate."""
        return {"docs": self._docs,
                "docs_split": self._docs_split,
                "docs_truncated": self._docs_truncated,
                "docs_empty": self._docs_empty,
                "tokens": self._tokens,
                "rows": self._rows,
                "seq_len": self.seq_len,
                "fill_rate": round(self.fill_rate, 4)}


def iter_packed_rows(docs: Iterable, seq_len: int,
                     packer: Optional[SequencePacker] = None,
                     finish: bool = True,
                     **packer_kwargs) -> Iterator[Dict[str, np.ndarray]]:
    """Pack a document iterable; yields completed rows in emission order.

    Pass an existing ``packer`` to read its accounting afterwards; to keep
    packing the SAME packer across several calls, pass ``finish=False`` on
    all but the last (``finish()`` closes the open bins and refuses further
    feeding).  An existing packer must agree with ``seq_len`` and takes no
    ``packer_kwargs`` - a silent mismatch would hand a jit consumer
    wrong-shaped blocks."""
    if packer is not None:
        if packer.seq_len != seq_len:
            raise PetastormTpuError(
                f"packer.seq_len {packer.seq_len} != seq_len {seq_len}:"
                " the packer's width wins silently otherwise")
        if packer_kwargs:
            raise PetastormTpuError(
                f"packer_kwargs {sorted(packer_kwargs)} are ignored when an"
                " existing packer is passed; configure the packer instead")
    p = packer if packer is not None else SequencePacker(seq_len,
                                                         **packer_kwargs)
    for doc in docs:
        yield from p.feed(doc)
    if finish:
        yield from p.finish()


def iter_packed_blocks(docs: Iterable, seq_len: int, batch_size: int,
                       packer: Optional[SequencePacker] = None,
                       drop_last: bool = False,
                       **packer_kwargs) -> Iterator[Dict[str, np.ndarray]]:
    """Pack documents into dense ``(batch, seq_len)`` blocks.

    Yields dicts of stacked ``tokens`` / ``segment_ids`` / ``positions`` /
    ``loss_mask`` arrays of shape ``(batch_size, seq_len)``.  The final
    block may have fewer rows (``drop_last=True`` drops it instead - the
    fixed-shape contract a jit consumer wants).
    """
    if batch_size < 1:
        raise PetastormTpuError("batch_size must be >= 1")
    pending: List[Dict[str, np.ndarray]] = []
    for row in iter_packed_rows(docs, seq_len, packer=packer,
                                **packer_kwargs):
        pending.append(row)
        if len(pending) == batch_size:
            yield {k: np.stack([r[k] for r in pending]) for k in pending[0]}
            pending = []
    if pending and not drop_last:
        yield {k: np.stack([r[k] for r in pending]) for k in pending[0]}


def iter_ragged_batches(docs: Iterable, batch_docs: int,
                        tokens_dtype=np.int32) -> Iterator[Dict[str, np.ndarray]]:
    """Ragged delivery for consumers that pack on-device: groups of
    ``batch_docs`` documents as one flat token buffer plus offsets.

    Yields ``{'tokens': (total,), 'offsets': (n+1,) int64, 'lengths': (n,)
    int32}`` - document ``i`` is ``tokens[offsets[i]:offsets[i+1]]``.  The
    final group may hold fewer documents.  Empty/None documents are kept as
    zero-length spans (the consumer sees the stream's true document count).
    """
    if batch_docs < 1:
        raise PetastormTpuError("batch_docs must be >= 1")
    tokens_dtype = np.dtype(tokens_dtype)
    group: List[np.ndarray] = []

    def _flush():
        lengths = np.asarray([len(d) for d in group], dtype=np.int32)
        offsets = np.zeros(len(group) + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        flat = (np.concatenate(group).astype(tokens_dtype, copy=False)
                if offsets[-1] else np.empty(0, dtype=tokens_dtype))
        return {"tokens": flat, "offsets": offsets, "lengths": lengths}

    for doc in docs:
        doc = (np.empty(0, dtype=tokens_dtype) if doc is None
               else np.asarray(doc).ravel())
        group.append(doc)
        if len(group) == batch_docs:
            yield _flush()
            group = []
    if group:
        yield _flush()


#: field emission order for the packed-stream digest (fixed, so the digest
#: never depends on dict ordering)
PACKED_FIELDS = ("tokens", "segment_ids", "positions", "loss_mask")


def packed_stream_digest(blocks: Iterable[Dict[str, np.ndarray]],
                         crc: int = 0) -> int:
    """Order-sensitive crc32 chain over a packed block stream - the packed
    analog of :class:`petastorm_tpu.seeding.StreamDigest`: two runs whose
    packed streams are bit-identical produce equal chains, in O(1) diff.

    Folds each block's shape and the four packed columns' bytes in the
    fixed :data:`PACKED_FIELDS` order; pass the previous return value as
    ``crc`` to chain across calls.
    """
    for block in blocks:
        rows, seq_len = np.asarray(block["tokens"]).shape
        crc = zlib.crc32(struct.pack("<2q", rows, seq_len), crc)
        for name in PACKED_FIELDS:
            col = np.ascontiguousarray(np.asarray(block[name]))
            crc = zlib.crc32(col.tobytes(), crc)
    return crc
