"""Fused on-chip uint8 -> float image normalization.

The op computes ``(u8/255 - mean[c]) / std[c]`` per channel, emitting bfloat16 by
default (MXU-native).  Shipping uint8 to the device and normalizing there cuts
host->device bytes 4x vs normalizing on host in float32 - on TPU the transfer is
usually the ingest bottleneck (HBM/PCIe bound), so this is the single highest-value
"decode on device" op (BASELINE.json north star).

Two implementations:

* ``_normalize_pallas``: a Pallas TPU kernel over (8, lane)-tiled blocks of the
  flattened (N, H*W*C) image, with per-position scale/bias vectors materialized
  once (channel pattern tiled across the row).  VPU-bound elementwise work with
  explicit VMEM blocking (see /opt/skills/guides/pallas_guide.md tiling table).
  In a program over a mesh of several devices the compiler cannot partition a
  Mosaic kernel, so the call runs under ``shard_map``: each device normalizes
  its own rows of the batch, with no gather.
* the plain jnp expression (XLA fuses this into one kernel too) - used on
  non-TPU backends and for shapes that violate the tiling constraints.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

_LANE = 128
_SUBLANE = 8


def _choose_block(n: int, length: int) -> Optional[Tuple[int, int]]:
    """(rows, cols) VMEM block for an (n, length) array, or None if untileable."""
    if length % _LANE != 0 or n % _SUBLANE != 0:
        return None
    bl = next((c for c in (8 * _LANE, 4 * _LANE, 2 * _LANE, _LANE)
               if length % c == 0), None)
    if bl is None:
        return None
    bn = next((r for r in (64, 32, 16, _SUBLANE) if n % r == 0), None)
    return (bn, bl) if bn else None


def _normalize_kernel(x_ref, scale_ref, bias_ref, o_ref):
    # Mosaic has no direct u8->f32 cast; widen through int32 first
    x = x_ref[:].astype(jnp.int32).astype(jnp.float32)
    o_ref[:] = (x * scale_ref[:] + bias_ref[:]).astype(o_ref.dtype)


def _normalize_pallas(flat_u8: jax.Array, scale_vec: jax.Array, bias_vec: jax.Array,
                      block: Tuple[int, int], out_dtype) -> jax.Array:
    from jax.experimental import pallas as pl

    n, length = flat_u8.shape
    bn, bl = block
    grid = (n // bn, length // bl)
    return pl.pallas_call(
        _normalize_kernel,
        out_shape=jax.ShapeDtypeStruct((n, length), out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bl), lambda i, j: (i, j)),
            pl.BlockSpec((1, bl), lambda i, j: (0, j)),
            pl.BlockSpec((1, bl), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bl), lambda i, j: (i, j)),
    )(flat_u8, scale_vec, bias_vec)


def _row_axes(images: jax.Array) -> Tuple[object, Tuple[str, ...]]:
    """``(mesh, axes)``: the mesh of the program that traces ``images`` and
    the mesh axes that shard its rows, or ``(None, ())`` in a one-device
    program.  Under jit an array's mesh is visible but, with the default
    (auto) axis types, not its spec; the rows are then taken to be sharded
    over the first mesh axis, as ``JaxDataLoader`` shards a batch when
    ``shardings`` names no axis.  A batch laid out otherwise is resharded
    around the kernel: the result is the same."""
    sharding = jax.typeof(images).sharding
    mesh = sharding.mesh
    if mesh.size <= 1:
        return None, ()
    lead = sharding.spec[0] if len(sharding.spec) else None
    if lead is None:
        lead = mesh.axis_names[0]
    return mesh, ((lead,) if isinstance(lead, str) else tuple(lead))


@functools.partial(jax.jit, static_argnames=("out_dtype", "use_pallas"))
def _normalize_impl(images: jax.Array, scale_vec: jax.Array, bias_vec: jax.Array,
                    out_dtype: jnp.dtype, use_pallas: bool) -> jax.Array:
    shape = images.shape
    flat = images.reshape(shape[0], -1)
    if use_pallas:
        mesh, axes = _row_axes(images)
        shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        block = (_choose_block(shape[0] // shards, flat.shape[1])
                 if shape[0] % shards == 0 else None)
        if block is not None:
            kernel = functools.partial(_normalize_pallas, block=block,
                                       out_dtype=out_dtype)
            if mesh is not None:
                rows = P(axes or None, None)
                # check_vma off: a pallas_call's output carries no
                # varying-axes type for shard_map to check
                kernel = jax.shard_map(kernel, mesh=mesh,
                                       in_specs=(rows, P(), P()),
                                       out_specs=rows, check_vma=False)
            return kernel(flat, scale_vec, bias_vec).reshape(shape)
    out = (flat.astype(jnp.float32) * scale_vec + bias_vec).astype(out_dtype)
    return out.reshape(shape)


def normalize_images(images: jax.Array,
                     mean: Sequence[float] = (0.485, 0.456, 0.406),
                     std: Sequence[float] = (0.229, 0.224, 0.225),
                     out_dtype=jnp.bfloat16) -> jax.Array:
    """``(images/255 - mean) / std`` fused on device; images are NHWC uint8.

    mean/std are per-channel in [0,1] units (torchvision convention).  On a
    TPU, uses the Pallas kernel when each device's rows satisfy the tiling;
    XLA elementwise otherwise (identical math).  In a program over several
    devices the kernel runs per device over the rows it holds.
    """
    if images.dtype != jnp.uint8:
        raise TypeError(f"normalize_images expects uint8, got {images.dtype}")
    if images.ndim < 2:
        raise TypeError("normalize_images expects at least (N, ...) images")
    c = images.shape[-1]
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    if mean.size == 1:
        mean = np.full(c, float(mean), np.float32)
    if std.size == 1:
        std = np.full(c, float(std), np.float32)
    if mean.size != c or std.size != c:
        raise ValueError(f"mean/std size {mean.size}/{std.size} != channels {c}")

    length = int(np.prod(images.shape[1:]))
    # per-position scale/bias row: channel pattern tiled across H*W
    scale_np = np.tile(1.0 / (255.0 * std), length // c).astype(np.float32)[None, :]
    bias_np = np.tile(-mean / std, length // c).astype(np.float32)[None, :]

    # trace-safe platform check: inside jit the array is abstract, so key off
    # the backend jit compiles for
    return _normalize_impl(images, jnp.asarray(scale_np), jnp.asarray(bias_np),
                           out_dtype, jax.default_backend() == "tpu")
