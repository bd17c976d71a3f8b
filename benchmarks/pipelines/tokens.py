"""Token cells: a pre-tokenized corpus made from the seed, packed by
``make_packed_sequence_loader`` into fixed-length rows on the chip, fed to
the configuration's consumer.

The mix's ``loader`` entry goes to ``make_packed_sequence_loader`` as it
stands (sequence length, long-document policy, reader options).  The
corpus is read in file order (``shuffle_row_groups`` false, a corpus
shuffled when it was written), so the packed stream is a function of the
corpus alone and the check compares every delivered row with the
reference packing.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

from benchmarks.harness import key_seed, log, seed_rng
from benchmarks.reference import tokens as ref

DONE = "_DONE"
FIELDS = ("tokens", "segment_ids", "positions", "loss_mask")


def write_corpus(path: str, seed: int, corpus: dict) -> int:
    """Documents with lognormal lengths and uniform token ids from the
    seed, as the program's ``token_field`` stores them.  Returns tokens."""
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.schema import Field, Schema
    from petastorm_tpu.sequence import token_field

    rng = seed_rng(seed, 4)
    n, sigma = corpus["docs"], corpus["sigma"]
    lengths = np.clip(rng.lognormal(np.log(corpus["mean_len"]) - sigma ** 2 / 2,
                                    sigma, n),
                      corpus["min_len"], corpus["max_len"]).astype(np.int64)
    tokens = rng.integers(0, corpus["vocab"], int(lengths.sum()),
                          dtype=np.int32)
    starts = np.concatenate([[0], np.cumsum(lengths)])
    schema = Schema("TokenCorpus", [Field("doc_id", np.int64),
                                    token_field("tokens", dtype=np.int32)])
    rows = ({"doc_id": i, "tokens": tokens[starts[i]:starts[i + 1]]}
            for i in range(n))
    write_dataset(path, schema, rows,
                  row_group_size_rows=corpus["docs_per_row_group"])
    return int(lengths.sum())


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
        self.batches, self.losses = [], []

    def prepare(self) -> None:
        if os.path.exists(os.path.join(self.data_dir, DONE)):
            return
        partial = self.data_dir + ".partial"
        shutil.rmtree(partial, ignore_errors=True)
        total = write_corpus(partial, self.seed, self.config["corpus"])
        log(f"corpus: {self.config['corpus']['docs']} documents,"
            f" {total} tokens")
        with open(os.path.join(partial, DONE), "w") as f:
            f.write(f"{total}\n")
        shutil.rmtree(self.data_dir, ignore_errors=True)
        os.replace(partial, self.data_dir)

    def open(self) -> None:
        from jax.sharding import PartitionSpec as P

        from petastorm_tpu.sequence import make_packed_sequence_loader

        self.consumer = self.consumer_mod.Consumer(
            self.config, self.mesh, self.batch, key_seed(self.seed))
        opts = dict(self.traffic["loader"])
        self.loader = make_packed_sequence_loader(
            self.data_dir, batch_size=self.batch,
            seq_len=self.config["model"]["block_size"],
            seed=int(seed_rng(self.seed, 3).integers(0, 2 ** 31)),
            num_epochs=None, telemetry=self.telemetry,
            loader_kwargs={"mesh": self.mesh,
                           "shardings": {k: P("data") for k in FIELDS}},
            **opts)
        self.it = iter(self.loader)

    def next_batch(self):
        return next(self.it)

    def step(self, batch, i):
        loss = self.consumer(batch)
        self.batches.append({k: batch[k] for k in FIELDS})
        self.losses.append(loss)
        return loss, None

    def counters(self) -> dict:
        if self.telemetry is None:
            return {}
        return dict(self.telemetry.snapshot()["counters"])

    def work(self, i: int) -> float:
        return float(self.delivered["loss_mask"][i].sum())

    def shapes(self) -> dict:
        return {"rows": self.batch, "chips": self.mesh.size,
                "seq_len": self.config["model"]["block_size"]}

    def programs(self) -> dict:
        return {"step": "jit_train_step"}

    def fetch(self, samples) -> dict:
        self.delivered = {k: np.stack([np.asarray(b[k]) for b in self.batches])
                          for k in FIELDS}
        losses = np.asarray([float(x) for x in self.losses])
        self.batches = self.losses = None
        return {"delivered": self.delivered, "losses": losses}

    def close(self) -> None:
        if self.loader is not None:
            self.loader.__exit__(None, None, None)
            self.loader = None
        if self.consumer is not None:
            self.consumer.close()

    def check(self, kept: dict) -> dict:
        """Every delivered row against the reference packing of the corpus
        read in file order: the count of values that differ."""
        got = kept["delivered"]
        steps, b, t = got["tokens"].shape
        opts = self.traffic["loader"]
        want = ref.packed_rows(ref.documents(self.data_dir), t,
                               opts.get("open_bins", 8), steps * b)
        bad = sum(int((got[k].reshape(steps * b, t) != want[k]).sum())
                  for k in FIELDS)
        log(f"check: {steps} steps of {b}x{t} packed rows")
        values = {"packed_mismatches": bad,
                  "nonfinite_losses": int((~np.isfinite(kept["losses"])).sum())}
        limits = self.config["limits"]
        return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
