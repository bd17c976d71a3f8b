"""Faults of the ILSVRC cell's own mechanism, for the teeth of its check:
the program with one guarantee of the canvas path broken, run through the
cell as ``control.py``'s variants are.

``crop_over_canvas``: the crop draws its boxes over the whole canvas, not
inside each image's extent.  ``crop_reads_outside``: the crop's bilinear
taps are not clamped to the box, so a sample past its last row or column
reads what lies beyond (the canvas padding where the box meets the
extent).  ``edges_zero``: the finish's fancy chroma upsampling reads zeros
past each image's own right and bottom edge instead of replicating its
last sample.  Any other name is one of ``control.py``'s variants (the
one-bf16-pass IDCT is its ``idct_default``).
"""

from __future__ import annotations

import contextlib

from benchmarks import control

FAULTS = ("crop_over_canvas", "crop_reads_outside", "edges_zero")


@contextlib.contextmanager
def variant(name: str, cell):
    """The program with fault ``name`` switched on, for the runs inside."""
    if name not in FAULTS:
        with control.variant(name, cell):
            yield
        return
    import jax
    import jax.numpy as jnp

    import petastorm_tpu.ops as ops
    import petastorm_tpu.ops.augment as augment
    import petastorm_tpu.ops.jpeg as jpeg

    saved = (ops.random_resized_crop, augment._taps, jpeg._edge_next)
    jax.clear_caches()
    try:
        if name == "crop_over_canvas":
            def over_canvas(images, key, out_hw, *args, extents=None, **kw):
                canvas = jnp.broadcast_to(
                    jnp.asarray(images.shape[1:3], jnp.int32),
                    extents.shape)
                return saved[0](images, key, out_hw, *args, extents=canvas,
                                **kw)

            ops.random_resized_crop = over_canvas
        elif name == "crop_reads_outside":
            def unclamped(start, size, out_len):
                i0, _, lam = saved[1](start, size, out_len)
                return i0, i0 + 1, lam

            augment._taps = unclamped
        else:
            jpeg._edge_next = lambda x, nxt, at_edge: jnp.where(
                at_edge, 0.0, nxt)
        yield
    finally:
        ops.random_resized_crop, augment._taps, jpeg._edge_next = saved
        jax.clear_caches()
