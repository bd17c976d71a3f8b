"""Ring attention over a sequence-sharded mesh axis (context parallelism).

Why this lives in an ingest framework: SURVEY.md section 2.14 - the reference has
no sequence parallelism at all, and the TPU build's contract is that the loader
emits per-host *sequence slices* (``tokens: P("data", "seq")``) for long-context
consumers.  This op is the consumer side of that contract: given the loader's
sequence-sharded batches, it computes exact softmax attention with each device
holding only ``S/P`` of the sequence, rotating K/V blocks around the mesh axis
with ``lax.ppermute`` (ICI neighbor exchange) and merging partial results with
the streaming log-sum-exp recurrence (flash-attention style), so no device ever
materializes the full S x S score matrix or the full sequence.

It both validates the CP feed path end-to-end (tests run it on the virtual
8-device mesh against a replicated reference) and serves as the building block
for long-context training loops fed by ``JaxDataLoader``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _merge(o, l, m, o_new, l_new, m_new):
    """Merge two partial attention results with log-sum-exp rescaling."""
    m_out = jnp.maximum(m, m_new)
    alpha = jnp.exp(m - m_out)
    beta = jnp.exp(m_new - m_out)
    l_out = l * alpha + l_new * beta
    o_out = o * alpha[..., None] + o_new * beta[..., None]
    return o_out, l_out, m_out


def _block_attention(q, k, v, scale, mask):
    """Partial attention of local q against one K/V block.

    q: (B, H, Sq, D); k/v: (B, H, Sk, D); mask: (Sq, Sk) bool or None.
    Returns unnormalized o (B, H, Sq, D), row sums l and row maxes m (B, H, Sq).
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)
    # rows that are fully masked (causal + remote future block) have m=-inf;
    # exp(-inf - -inf) would be NaN, so clamp the shift to a finite value
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, l, jnp.where(jnp.isfinite(m), m, -jnp.inf)


def ring_attention_sharded(q, k, v, axis_name: str, causal: bool = False,
                           scale: Optional[float] = None):
    """Exact attention with K/V ring-rotated over ``axis_name``.

    Call INSIDE ``shard_map`` where q/k/v are the local sequence slices, laid
    out (B, H, S_local, D).  The sequence axis must be sharded contiguously in
    mesh order (exactly what ``JaxDataLoader`` emits for ``P(..., axis_name)``).

    Per ring step each device computes one block of the streaming-softmax
    recurrence, then passes its K/V block to the next device
    (``ppermute`` rides ICI on TPU).  Communication per device is
    ``2 * S_local * H * D`` elements per step - the standard ring-attention
    cost model (PAPERS.md: Ring Attention with Blockwise Transformers).
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    q_pos = my_idx * s_local + jnp.arange(s_local)

    # derive the initial carry from q so shard_map marks it device-varying
    # (a plain zeros() constant has mismatched varying axes in the scan carry)
    o0 = (q * 0.0).astype(jnp.float32)
    l0 = o0[..., 0]
    m0 = l0 - jnp.inf

    def step(t, carry):
        o, l, m, k_blk, v_blk = carry
        # after t rotations device i holds the block that started at (i - t)
        src = (my_idx - t) % axis_size

        def attend(o, l, m):
            if causal:
                k_pos = src * s_local + jnp.arange(s_local)
                mask = k_pos[None, :] <= q_pos[:, None]
            else:
                mask = None
            o_new, l_new, m_new = _block_attention(
                q.astype(jnp.float32), k_blk.astype(jnp.float32),
                v_blk.astype(jnp.float32), scale, mask)
            return _merge(o, l, m, o_new, l_new, m_new)

        if causal:
            # blocks entirely in the future are fully masked: skip both
            # einsums (~half the FLOPs for long-context causal training);
            # the ppermute below still runs every step to keep the ring moving
            o, l, m = jax.lax.cond(src <= my_idx, attend,
                                   lambda o, l, m: (o, l, m), o, l, m)
        else:
            o, l, m = attend(o, l, m)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, l, m, k_blk, v_blk

    o, l, m, _, _ = jax.lax.fori_loop(0, axis_size, step, (o0, l0, m0, k, v))
    # fully-masked rows (can't happen with causal self-attention over the own
    # block, but guard anyway) divide by 1 instead of 0
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l[..., None]).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("mesh", "seq_axis", "batch_axes",
                                             "causal", "scale"))
def ring_attention(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                   batch_axes: tuple = ("data",), causal: bool = False,
                   scale: Optional[float] = None):
    """Mesh-level entry point: q/k/v are global arrays (B, H, S, D) with the
    sequence dim sharded over ``seq_axis`` (e.g. the loader's
    ``shardings={"tokens": P("data", "seq")}`` delivery), batch over
    ``batch_axes``.  Heads/feature stay replicated over ``seq_axis``."""
    spec = P(batch_axes, None, seq_axis, None)
    inner = functools.partial(ring_attention_sharded, axis_name=seq_axis,
                              causal=causal, scale=scale)
    fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.lax.with_sharding_constraint(x, sharding) for x in (q, k, v))
    return fn(q, k, v)
