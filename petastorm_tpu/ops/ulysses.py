"""Ulysses-style sequence parallelism: all-to-all head/sequence resharding.

The second of the two standard context-parallel attention strategies
(SURVEY.md's long-context mandate: "ring attention or all-to-all
sequence/context parallelism"; PAPERS.md: DeepSpeed-Ulysses).  Where ring
attention keeps the sequence sharded and rotates K/V blocks P-1 times,
Ulysses pays exactly TWO collectives: an ``all_to_all`` that re-shards from
sequence-sharded (every device holds S/P of all H heads) to head-sharded
(every device holds ALL of the sequence for H/P heads), then plain full
attention locally, then the inverse ``all_to_all``.

Trade-off (why both exist):

* Ulysses moves ``3 * S/P * H * D`` in one shot and computes dense local
  attention - fewer, bigger collectives, but requires ``H % P == 0`` and each
  device materializes full-S activations for its heads (memory ~ S).
* Ring never materializes full S anywhere (memory ~ S/P) and has no head
  divisibility constraint, but runs P-1 neighbor exchanges.

Both consume the SAME loader delivery: sequence-sharded batches
(``shardings={"tokens": P("data", "seq")}``) - which is the point of hosting
them here: they validate the CP feed contract end-to-end.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _full_attention(q, k, v, scale, causal):
    """Dense softmax attention, (B, H, S, D) all-local."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = q.shape[2]
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def ulysses_attention_sharded(q, k, v, axis_name: str, causal: bool = False,
                              scale: Optional[float] = None):
    """Call INSIDE ``shard_map``: q/k/v are local sequence slices
    (B, H, S_local, D) with the head count divisible by the axis size.

    Collective #1: q/k/v stacked into ONE ``all_to_all``
    (3, B, H, S/P, D) -> (3, B, H/P, S, D)  [heads scatter, sequence
    gathers]; local dense attention (float32 accumulation, matching
    ring_attention's numerics); collective #2 inverts for the output.
    """
    p = jax.lax.psum(1, axis_name)
    b, h, s_local, d = q.shape
    if h % p:
        raise ValueError(
            f"Ulysses needs heads ({h}) divisible by the '{axis_name}' axis"
            f" size ({p}); use ring_attention for indivisible head counts")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    out_dtype = q.dtype

    qkv = jnp.stack([q, k, v])  # one collective for all three
    qkv = jax.lax.all_to_all(qkv, axis_name, split_axis=2, concat_axis=3,
                             tiled=True)  # (3, B, H/P, S, D)
    q, k, v = (x.astype(jnp.float32) for x in qkv)
    o = _full_attention(q, k, v, scale, causal)  # (B, H/P, S, D) f32
    o = jax.lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)           # (B, H, S/P, D)
    return o.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("mesh", "seq_axis", "batch_axes",
                                             "causal", "scale"))
def ulysses_attention(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                      batch_axes: tuple = ("data",), causal: bool = False,
                      scale: Optional[float] = None):
    """Mesh-level entry point, same contract as ``ops.ring_attention``:
    q/k/v are global (B, H, S, D) arrays with the sequence dim sharded over
    ``seq_axis`` (the loader's ``P("data", "seq")`` delivery), batch over
    ``batch_axes``; heads must be divisible by the ``seq_axis`` size."""
    spec = P(batch_axes, None, seq_axis, None)
    inner = functools.partial(ulysses_attention_sharded, axis_name=seq_axis,
                              causal=causal, scale=scale)
    fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.lax.with_sharding_constraint(x, sharding) for x in (q, k, v))
    return fn(q, k, v)
