"""Readings for the limits of the check: the program on many seeds, and
the control on a few, each a short run of the cell in this one process.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 --seconds 5 \
        [--variant <name>] ...

``none`` is the program as it is.  ``idct_default`` is the control of the
image cells: the inverse DCT in one bf16 pass, the precision below the
float32 the configuration states; ``nearest_upsampling`` breaks its other
stated guarantee, the program's own nearest-neighbour chroma path where
the configuration states libjpeg's fancy upsampling; ``idct_high`` (three
passes, within float32) is a reading only.  ``truncate_long_docs`` is the
control of the token cells: the program's own truncating path, where the
configuration states that every token of every document is delivered.
One JSON line per run.

The faults break the timed path underneath, where the program hands the
consumer its batch (``JaxDataLoader.__next__``) or normalizes it:
``answer_altered`` (a row's label or token moved by one),
``half_batch`` (the second half of the batch replaced by the first),
``no_exchange`` (every chip given the first chip's rows),
``state_unchanged`` (the same batch every step), ``normalize_bf16``
(the normalize computed in bfloat16, below the float32 it states) and
``nonfinite_loss`` (every step's loss turned to NaN where the consumer
returns it).
"""

import argparse
import contextlib
import functools
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))


def _altered(name, v):
    return v.at[0].add(1) if name in ("label", "tokens") else v


def _half(name, v):
    import jax.numpy as jnp

    h = v.shape[0] // 2
    return jnp.concatenate([v[:h], v[:h]])


def _no_exchange(name, v):
    import jax
    import jax.numpy as jnp

    n = len(v.sharding.device_set)
    rows = v.shape[0] // n
    return jax.device_put(jnp.concatenate([v[:rows]] * n), v.sharding)


BATCH_FAULTS = {"answer_altered": _altered, "half_batch": _half,
                "no_exchange": _no_exchange}


@contextlib.contextmanager
def variant(name: str, cell):
    """The program with the variant switched on, for the runs inside."""
    import jax
    import jax.numpy as jnp

    import petastorm_tpu.ops as ops
    import petastorm_tpu.ops.jpeg as jpeg
    from petastorm_tpu.jax.loader import JaxDataLoader

    consumer = cell.consumer().Consumer
    saved = (jpeg.decode_coefficients, dict(cell.traffic.get("loader", {})),
             JaxDataLoader.__next__, ops.normalize_images,
             consumer.__call__)
    jax.clear_caches()
    try:
        if name == "nearest_upsampling":
            jpeg.decode_coefficients = functools.partial(
                saved[0], fancy_upsampling=False)
        elif name in ("idct_high", "idct_default"):
            low = {"idct_high": jax.lax.Precision.HIGH,
                   "idct_default": jax.lax.Precision.DEFAULT}[name]

            def einsum(spec, *operands, precision=None, **kw):
                if low != jax.lax.Precision.DEFAULT:
                    return jnp.einsum(spec, *operands, precision=low, **kw)
                # one bf16 pass per product, as the TPU computes it at this
                # precision, made explicit so that the CPU computes it too:
                # each operand of each of the two products kept to bf16
                assert spec == "...uv,uk,vl->...kl", spec
                x, a, b = (o.astype(jnp.bfloat16) for o in operands)
                t = jnp.einsum("...uv,uk->...kv", x, a,
                               preferred_element_type=jnp.float32)
                return jnp.einsum("...kv,vl->...kl", t.astype(jnp.bfloat16),
                                  b, preferred_element_type=jnp.float32)

            # the IDCT's einsum only; the colour matmul keeps HIGHEST
            jpeg.jnp = _Shim(jnp, einsum=einsum)
        elif name == "truncate_long_docs":
            cell.traffic["loader"]["long_docs"] = "truncate"
        elif name == "normalize_bf16":
            def normalize_bf16(images):
                mean = jnp.asarray([0.485, 0.456, 0.406], jnp.bfloat16)
                std = jnp.asarray([0.229, 0.224, 0.225], jnp.bfloat16)
                x = images.astype(jnp.bfloat16)
                return (x / jnp.bfloat16(255) - mean) / std

            ops.normalize_images = normalize_bf16
        elif name == "nonfinite_loss":
            def nan_loss(self, *args):
                out = saved[4](self, *args)
                if isinstance(out, tuple):
                    return (out[0] * jnp.nan,) + out[1:]
                return out * jnp.nan

            consumer.__call__ = nan_loss
        elif name in BATCH_FAULTS or name == "state_unchanged":
            real, first = saved[2], []

            def broken(self):
                batch = real(self)
                if name == "state_unchanged":
                    first.append(first[0] if first else batch)
                    return first[-1]
                return {k: BATCH_FAULTS[name](k, v) for k, v in batch.items()}

            JaxDataLoader.__next__ = broken
        elif name != "none":
            raise ValueError(f"unknown variant {name!r}")
        yield
    finally:
        jpeg.decode_coefficients = saved[0]
        jpeg.jnp = jnp
        if "loader" in cell.traffic:
            cell.traffic["loader"] = saved[1]
        JaxDataLoader.__next__ = saved[2]
        ops.normalize_images = saved[3]
        consumer.__call__ = saved[4]
        jax.clear_caches()


class _Shim:
    """``jax.numpy`` with some attributes replaced."""

    def __init__(self, base, **over):
        self._base, self._over = base, over

    def __getattr__(self, name):
        return self._over.get(name, getattr(self._base, name))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--variant", action="append", default=None)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    from benchmarks import harness, spec
    from benchmarks.run import build_native, setup_compile_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    setup_compile_cache()
    import jax

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print("no TPU: use --rehearse", file=sys.stderr)
        return 2
    build_native()
    for name in args.variant or ["none"]:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            with variant(name, cell):
                out = harness.run_cell(cell, seed, args.seconds, False,
                                       args.rehearse, t0, devices)
            print(json.dumps({"variant": name, "seed": seed,
                              "correct": out["correct"],
                              "checks": {k: c["value"] for k, c
                                         in out["checks"].items()},
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
