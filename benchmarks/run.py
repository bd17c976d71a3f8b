"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Off a TPU, or with fewer chips than the cell asks for, it exits 2 with no
result.  ``--rehearse`` runs every phase at the configuration's tiny
rehearsal sizes wherever JAX runs (the CPU here), then exits 1 with no
result: it never prints a metric.
"""

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on any backend; never prints a result")
    return p.parse_args(argv)


def setup_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else a fixed directory in the checkout (the path is part of the key)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        BENCH_DIR, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, so set-up is the same work
    # in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_native() -> None:
    """The program's native libraries, built once per checkout."""
    from petastorm_tpu.native import build

    for name in ("shm_arena", "image_decode"):
        if build.build(name) is None:
            raise RuntimeError(f"native build of {name} failed")


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness, spec

    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    setup_compile_cache()
    import jax

    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) >= cell.chips
    if not on_chip and not args.rehearse:
        print(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found"
              f" {len(devices)} {devices[0].platform} device(s): no result",
              file=sys.stderr)
        return 2
    if args.rehearse and len(devices) < cell.chips:
        print(f"rehearsal of {args.workload} needs {cell.chips} devices"
              " (XLA_FLAGS=--xla_force_host_platform_device_count=N)",
              file=sys.stderr)
        return 2
    build_native()
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              args.rehearse, T_START, devices)
    for name, c in result["checks"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})"
                    f" {'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    if args.rehearse or not on_chip:
        harness.log(f"rehearsal ran every phase on {devices[0].platform}:"
                    " no result")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
