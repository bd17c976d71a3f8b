"""Smoke run of the main ingest path on a TPU, through the entry points a
user calls.

One chip (no arguments): an ImageNet-shaped JPEG dataset is generated from
``--seed``; ``make_reader(decode_placement={"image": "device"})`` runs the
entropy decode in host workers and the rest of the decode on the chip;
``JaxDataLoader`` delivers batches into a one-device mesh; the ResNet-50
train step of ``examples/imagenet/train_resnet_tpu.py`` (crop, flip, the
Pallas normalize, forward, backward, SGD with momentum) takes warm-up steps
and then ``--steps`` more.  Every phase checks its result and raises on
failure.

``--chips 4`` runs only the four-chip path and what it is compared with:
the same feed into a 4-device mesh, data-parallel ResNet-50 steps checked
against the same batch on one device, and sequence-sharded tokens into
ring and Ulysses attention checked against dense attention.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
on a TPU.  Off a TPU the script exits 1 with no result: at once at the full
width, or, with tiny size flags (``JAX_PLATFORMS=cpu ... --side 32``),
after running every phase as a rehearsal.  The size flags exist for that
rehearsal; their defaults are the full width.
"""

import argparse
import json
import os
import signal
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
FULL_SIDE = 224


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Times one phase, prints its outcome, and bounds it with SIGALRM: a
    phase that hangs (a child waiting on a chip it cannot have) fails."""

    def __init__(self, name: str, timeout_s: int = 600):
        self.name, self.timeout_s = name, timeout_s

    def _expired(self, *_):
        raise TimeoutError(f"phase {self.name!r} exceeded {self.timeout_s}s")

    def __enter__(self):
        log(f"[{self.name}] ...")
        signal.signal(signal.SIGALRM, self._expired)
        signal.alarm(self.timeout_s)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        signal.alarm(0)
        state = "FAILED" if exc_type else "ok"
        log(f"[{self.name}] {state} in {time.perf_counter() - self.t0:.2f} s")
        return False  # never swallow the failure


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 = only the four-chip path and its comparisons")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rows", type=int, default=1024)
    p.add_argument("--side", type=int, default=FULL_SIDE)
    p.add_argument("--batch", type=int, default=32, help="rows per chip")
    p.add_argument("--steps", type=int, default=10,
                   help="measured steps after the warm-up")
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--workers", type=int, default=4)
    p.add_argument("--seq", type=int, default=2048,
                   help="--chips 4: tokens per sequence for attention")
    return p.parse_args(argv)


def build_native() -> None:
    """Both native libraries, compiled from the sources in git; a failed
    build fails the run."""
    from petastorm_tpu.native import build

    for name in ("shm_arena", "image_decode"):
        path = build.build(name)
        if path is None:
            raise RuntimeError(f"native build of {name} failed")
        log(f"  native {name}: {os.path.relpath(path, ROOT)}")


def make_dataset(tmp: str, args) -> str:
    from examples.imagenet.train_resnet_tpu import generate_dataset

    url = os.path.join(tmp, "imagenet")
    generate_dataset(url, args.rows, args.side, seed=args.seed)
    files = [os.path.join(url, f) for f in os.listdir(url)
             if f.endswith(".parquet")]
    import pyarrow.parquet as pq

    groups = sum(pq.ParquetFile(f).num_row_groups for f in files)
    size = sum(os.path.getsize(f) for f in files)
    log(f"  {args.rows} rows at {args.side}x{args.side} jpeg q90 in"
        f" {groups} rowgroups, {size / args.rows / 1024:.1f} KiB per row")
    if groups < 2:
        raise AssertionError(f"expected several rowgroups, got {groups}")
    return url


def host_decoded(url: str, ids) -> dict:
    """The same rows decoded on the host (the codec's cv2/native path),
    keyed by row id."""
    from petastorm_tpu.reader import make_reader

    want, found = set(int(i) for i in ids), {}
    with make_reader(url, schema_fields=["id", "image"], num_epochs=1,
                     workers_count=1, shuffle_row_groups=False) as reader:
        for row in reader:
            if int(row.id) in want:
                found[int(row.id)] = row.image
    if set(found) != want:
        raise AssertionError(f"host decode missed ids {sorted(want - set(found))}")
    return found


def check_hybrid_decode(batch, url: str) -> None:
    import numpy as np

    ids = np.asarray(batch["id"])
    dev = np.asarray(batch["image"])
    host = host_decoded(url, ids)
    diff = np.abs(dev.astype(np.int16)
                  - np.stack([host[int(i)] for i in ids]).astype(np.int16))
    log(f"  device vs host decode over {len(ids)} rows matched by id:"
        f" max |diff| {int(diff.max())}, mean {float(diff.mean()):.4f}")
    if diff.max() > 6 or diff.mean() >= 1:
        raise AssertionError("hybrid decode disagrees with the host decode")


def check_normalize(images, on_tpu: bool) -> None:
    """The Pallas kernel is in the compiled normalize, and agrees with the
    plain XLA expression on the same on-device batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petastorm_tpu.ops import normalize_images
    from petastorm_tpu.ops.normalize import _choose_block

    mean = jnp.asarray([0.485, 0.456, 0.406], jnp.float32)
    std = jnp.asarray([0.229, 0.224, 0.225], jnp.float32)
    n = images.shape[0]
    if _choose_block(n, int(np.prod(images.shape[1:]))) is None:
        raise AssertionError(f"shape {images.shape} cannot take the kernel")
    kernel = jax.jit(normalize_images)
    text = kernel.lower(images).compile().as_text()
    has_kernel = "tpu_custom_call" in text
    log(f"  tpu_custom_call in compiled normalize: {has_kernel}")
    if on_tpu and not has_kernel:
        raise AssertionError("normalize_images did not take the Pallas kernel")
    plain = jax.jit(lambda x: ((x.astype(jnp.float32) / 255.0 - mean)
                               / std).astype(jnp.bfloat16))
    a = np.asarray(kernel(images), np.float32)
    b = np.asarray(plain(images), np.float32)
    err = float(np.abs(a - b).max())
    # one bf16 step at the largest |value| (< 4): 2**-6
    log(f"  kernel vs XLA expression: max |diff| {err}")
    if err > 2.0 ** -6:
        raise AssertionError("Pallas normalize disagrees with XLA beyond bf16")


def run_one_chip(args, url: str, on_tpu: bool) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from examples.imagenet.train_resnet_tpu import make_feed, make_train_step
    from petastorm_tpu.jax.compile_cache import cache_counts

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with Phase("model init"):
        params, opt_state, train_step, _ = make_train_step(mesh, args.side,
                                                           seed=args.seed)
        leaf0 = np.asarray(jax.tree.leaves(params)[0]).copy()
    feed = make_feed(url, mesh, args.batch, decode="device",
                     workers=args.workers)
    with feed:
        it = iter(feed)
        with Phase("first batch, hybrid decode check"):
            first = next(it)
            log(f"  image {first['image'].shape} {first['image'].dtype}"
                f" on {first['image'].sharding.device_set}")
            check_hybrid_decode(first, url)
        with Phase("normalize kernel check"):
            check_normalize(first["image"], on_tpu)
        key = jax.random.PRNGKey(args.seed)
        with Phase("train step compile"):
            before = cache_counts()
            t0 = time.perf_counter()
            exe = train_step.lower(params, opt_state, first["image"],
                                   first["label"], key).compile()
            compile_s = time.perf_counter() - t0
            after = cache_counts()
            hit = (after["hits"] > before["hits"]
                   and after["misses"] == before["misses"])
            has_kernel = "tpu_custom_call" in exe.as_text()
            log(f"  step compile {compile_s:.2f} s, compile cache"
                f" {'hit' if hit else 'miss'} (hits"
                f" +{after['hits'] - before['hits']}, misses"
                f" +{after['misses'] - before['misses']})")
            log(f"  tpu_custom_call in compiled train step: {has_kernel}")
            if on_tpu and not has_kernel:
                raise AssertionError("train step lost the Pallas normalize")
        losses = []

        def step(batch, i):
            nonlocal params, opt_state
            params, opt_state, loss = exe(params, opt_state, batch["image"],
                                          batch["label"],
                                          jax.random.fold_in(key, i))
            loss.block_until_ready()
            losses.append(float(loss))

        with Phase("warm-up steps (set-up)"):
            t0 = time.perf_counter()
            step(first, 0)
            for i in range(1, args.warmup):
                step(next(it), i)
            log(f"  {args.warmup} warm-up steps {time.perf_counter() - t0:.3f} s")
        with Phase("measured steps"):
            waits, steps = [], []
            for i in range(args.steps):
                t0 = time.perf_counter()
                batch = next(it)
                t1 = time.perf_counter()
                step(batch, args.warmup + i)
                waits.append(t1 - t0)
                steps.append(time.perf_counter() - t1)
            log(f"  step time (dispatch to block_until_ready), s: median"
                f" {sorted(steps)[len(steps) // 2]:.4f}, min {min(steps):.4f},"
                f" max {max(steps):.4f}")
            log(f"  wait for the next batch, s: median"
                f" {sorted(waits)[len(waits) // 2]:.4f}, max {max(waits):.4f}")
            log(f"  losses: {[round(x, 4) for x in losses]}")
            if not all(np.isfinite(losses)):
                raise AssertionError("non-finite loss")
            moved = float(np.abs(np.asarray(jax.tree.leaves(params)[0])
                                 - leaf0).max())
            log(f"  first parameter leaf moved by max {moved:.3e}")
            if not moved > 0:
                raise AssertionError("parameters did not change")
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log(f"  peak_bytes_in_use: {peak if peak is not None else 'not reported'}"
            + (f" ({peak / 2**30:.3f} GiB)" if peak else ""))
    with Phase("process pool feed (children stay off the chip)"):
        with make_feed(url, mesh, args.batch, decode="device", workers=2,
                       pool_type="process") as pfeed:
            pit = iter(pfeed)
            for i in range(3):
                batch = next(pit)
                if batch["image"].shape != first["image"].shape:
                    raise AssertionError(f"bad batch {batch['image'].shape}")
                step(batch, 1000 + i)
            log(f"  3 batches from 2 process workers, losses"
                f" {[round(x, 4) for x in losses[-3:]]}")
            if not all(np.isfinite(losses)):
                raise AssertionError("non-finite loss")


def run_four_chips(args, url: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from examples.imagenet.train_resnet_tpu import make_feed, make_train_step

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, have {len(devices)}")
    devices = devices[:4]
    mesh = Mesh(np.asarray(devices), ("data",))
    global_batch = 4 * args.batch
    with Phase("4-chip feed and data-parallel ResNet-50"):
        params, opt_state, train_step, _ = make_train_step(mesh, args.side,
                                                           seed=args.seed)
        with make_feed(url, mesh, global_batch, decode="device",
                       workers=args.workers) as feed:
            it = iter(feed)
            first = next(it)
            for name in ("image", "label", "id"):
                shards = first[name].addressable_shards
                on = {s.device for s in shards}
                rows = {s.data.shape[0] for s in shards}
                log(f"  {name} {first[name].shape}: {len(shards)} shards on"
                    f" {len(on)} devices, {rows} rows each")
                if len(on) != 4 or rows != {args.batch}:
                    raise AssertionError(f"{name} not sharded over 4 devices")
            # the same global batch, params and key on devices[0] alone
            one = jax.sharding.SingleDeviceSharding(devices[0])
            host = jax.device_get((params, opt_state, first["image"],
                                   first["label"]))
            p1, o1, img1, lbl1 = jax.device_put(host, one)
            key = jax.random.PRNGKey(args.seed)
            t0 = time.perf_counter()
            _, _, ref = train_step(p1, o1, img1, lbl1, key)
            ref = float(ref)
            log(f"  one-device step on {global_batch} rows:"
                f" {time.perf_counter() - t0:.2f} s incl. compile")
            losses = []
            t0 = time.perf_counter()
            for i, batch in enumerate([first] + [next(it) for _ in range(2)]):
                params, opt_state, loss = train_step(
                    params, opt_state, batch["image"], batch["label"],
                    jax.random.fold_in(key, i) if i else key)
                losses.append(float(loss))
            log(f"  3 data-parallel steps {time.perf_counter() - t0:.2f} s"
                f" incl. compile, losses {[round(x, 4) for x in losses]}")
            log(f"  first-step loss: 4 chips {losses[0]:.5f}, one device"
                f" {ref:.5f}, |diff| {abs(losses[0] - ref):.2e}")
            if not all(np.isfinite(losses)):
                raise AssertionError("non-finite loss")
            if abs(losses[0] - ref) > 1e-2 * max(1.0, abs(ref)):
                raise AssertionError("data-parallel loss disagrees with the"
                                     " one-device loss")
    with Phase("sequence-sharded tokens into ring and Ulysses attention"):
        from petastorm_tpu.etl.writer import write_dataset
        from petastorm_tpu.jax import JaxDataLoader
        from petastorm_tpu.ops import ring_attention, ulysses_attention
        from petastorm_tpu.reader import make_reader
        from petastorm_tpu.schema import Field, Schema

        seq, vocab, heads, hdim, batch = args.seq, 32000, 8, 128, 4
        rng = np.random.default_rng(args.seed)
        tok_url = os.path.join(os.path.dirname(url), "tokens")
        write_dataset(tok_url, Schema("Tokens", [Field("tokens", np.int32,
                                                       (seq,))]),
                      [{"tokens": rng.integers(0, vocab, seq).astype(np.int32)}
                       for _ in range(4 * batch)], row_group_size_rows=batch)
        cp_mesh = Mesh(np.asarray(devices).reshape(2, 2), ("data", "seq"))
        with JaxDataLoader(make_reader(tok_url, num_epochs=1,
                                       shuffle_row_groups=False),
                           batch_size=batch, mesh=cp_mesh,
                           shardings={"tokens": P("data", "seq")}) as loader:
            tokens = next(iter(loader))["tokens"]
        on = {s.device for s in tokens.addressable_shards}
        log(f"  tokens {tokens.shape} {tokens.sharding.spec} on"
            f" {len(on)} devices")
        if len(on) != 4 or tokens.sharding.spec != P("data", "seq"):
            raise AssertionError("tokens not sharded (data, seq) over 4 devices")
        emb = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(args.seed),
                              (3, vocab, heads * hdim), jnp.float32) * 0.5,
            NamedSharding(cp_mesh, P()))

        @jax.jit
        def qkv(emb, tokens):
            b, s = tokens.shape
            return tuple(emb[i][tokens].reshape(b, s, heads, hdim)
                         .transpose(0, 2, 1, 3) for i in range(3))

        def dense(q, k, v):
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hdim)
            mask = jnp.tril(jnp.ones((q.shape[2], q.shape[2]), bool))
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

        with jax.default_matmul_precision("highest"):
            q, k, v = qkv(emb, tokens)
            want = np.asarray(jax.jit(dense)(q, k, v))
            for name, fn in (("ring", ring_attention),
                             ("ulysses", ulysses_attention)):
                got = np.asarray(fn(q, k, v, mesh=cp_mesh, seq_axis="seq",
                                    causal=True))
                err = float(np.abs(got - want).max())
                log(f"  {name} vs dense attention: max |diff| {err:.2e}")
                if err > 2e-3:
                    raise AssertionError(f"{name} attention disagrees with"
                                         " dense attention")


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, ROOT)
    with Phase("native build from git sources"):
        build_native()
    from petastorm_tpu.jax.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    on_tpu = dev["platform"] == "tpu"
    log(f"devices: {dev}; compile cache {cache_dir}")
    if not on_tpu and args.side >= FULL_SIDE:
        print(f"platform {dev['platform']!r} is not a TPU: no result (rehearse"
              " off the chip with tiny size flags, e.g. --side 32)",
              file=sys.stderr)
        return 1
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        with Phase("dataset generation"):
            url = make_dataset(tmp, args)
        if args.chips == 4:
            run_four_chips(args, url)
        else:
            run_one_chip(args, url, on_tpu)
    if not on_tpu:
        print(f"every phase ran, but on platform {dev['platform']!r}, not a"
              " TPU: no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
