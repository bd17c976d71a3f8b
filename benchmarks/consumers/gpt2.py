"""The token cells' consumer: one GPT-2 small training step in plain
``jax.numpy``, as nanoGPT's ``config/train_gpt2.py`` trains it (no biases,
vocabulary padded to 50,304, AdamW with betas 0.9/0.95, weight decay 0.1
on matrices, gradient clipping at 1.0), with bfloat16 compute over float32
master weights.

Two departures from nanoGPT, both stated in the configuration: attention
is causal *within each packed segment*, and learned position embeddings
are read at the packed ``positions`` (nanoGPT neither masks across
documents nor resets positions); and the update runs every step, where
nanoGPT accumulates 40 micro-steps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P


def _layer_norm(x, g, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * g).astype(x.dtype)


def init_params(key, m: dict) -> dict:
    d, L, V, T = m["n_embd"], m["n_layer"], m["vocab_size"], m["block_size"]
    k = jax.random.split(key, 6)
    std, proj_std = 0.02, 0.02 / (2 * L) ** 0.5
    normal = jax.random.normal
    return {
        "wte": normal(k[0], (V, d), jnp.float32) * std,
        "wpe": normal(k[1], (T, d), jnp.float32) * std,
        "ln_f": jnp.ones((d,), jnp.float32),
        "blocks": {
            "ln_1": jnp.ones((L, d), jnp.float32),
            "c_attn": normal(k[2], (L, d, 3 * d), jnp.float32) * std,
            "attn_proj": normal(k[3], (L, d, d), jnp.float32) * proj_std,
            "ln_2": jnp.ones((L, d), jnp.float32),
            "c_fc": normal(k[4], (L, d, 4 * d), jnp.float32) * std,
            "mlp_proj": normal(k[5], (L, 4 * d, d), jnp.float32) * proj_std,
        },
    }


def loss_fn(params, tokens, segment_ids, positions, loss_mask, n_head):
    """Mean next-token cross-entropy over the tokens whose successor lies
    in the same segment."""
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    b, t = tokens.shape
    d = p["wte"].shape[1]
    hd = d // n_head
    x = p["wte"][tokens] + p["wpe"][positions]
    same = segment_ids[:, :, None] == segment_ids[:, None, :]
    causal = jnp.tril(jnp.ones((t, t), bool))
    mask = (same & causal[None] & (segment_ids[:, :, None] > 0))[:, None]

    def layer(x, w):
        h = _layer_norm(x, w["ln_1"])
        q, k, v = jnp.split(h @ w["c_attn"], 3, axis=-1)
        q, k, v = (a.reshape(b, t, n_head, hd) for a in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = jnp.where(mask, s / hd ** 0.5, -1e30)
        a = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, t, d)
        x = x + o @ w["attn_proj"]
        h = _layer_norm(x, w["ln_2"])
        x = x + jax.nn.gelu(h @ w["c_fc"], approximate=True) @ w["mlp_proj"]
        return x, None

    # the attention scores of every layer would not fit beside the rest at
    # nanoGPT's batch: keep the projections' outputs, recompute attention
    layer = jax.checkpoint(
        layer, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    x, _ = jax.lax.scan(layer, x, p["blocks"])
    x = _layer_norm(x, p["ln_f"])
    logits = (x[:, :-1] @ p["wte"].T).astype(jnp.float32)
    target = tokens[:, 1:]
    valid = ((segment_ids[:, 1:] == segment_ids[:, :-1])
             & (segment_ids[:, 1:] > 0)).astype(jnp.float32)
    valid = valid * loss_mask[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    return ((logz - picked) * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def build(config: dict, batch: int):
    """(init, train_step): ``init(key)`` makes (params, opt_state) from a
    key; ``train_step`` is the step, to be jitted."""
    m = config["model"]
    o = m["optimizer"]

    def decay(params):  # weight decay on matrices only, as nanoGPT
        return jax.tree.map(lambda a: a.ndim >= 2, params)

    tx = optax.chain(
        optax.clip_by_global_norm(o["grad_clip"]),
        optax.adamw(o["learning_rate"], b1=o["beta1"], b2=o["beta2"],
                    weight_decay=o["weight_decay"], mask=decay))
    n_head = m["n_head"]

    def init(key):
        params = init_params(key, m)
        return params, tx.init(params)

    def train_step(params, opt_state, tokens, segment_ids, positions,
                   loss_mask):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, segment_ids, positions, loss_mask, n_head)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return init, train_step


class Consumer:
    """``state`` is (params, opt_state), made on the device from the seed in
    one compiled call and replicated over the mesh; ``step`` is the jitted
    train step (``jit_train_step`` in the trace)."""

    def __init__(self, config: dict, mesh, batch: int, seed: int):
        init, train_step = build(config, batch)
        self.state = jax.jit(init, out_shardings=NamedSharding(mesh, P()))(
            jax.random.PRNGKey(seed))
        self.step = jax.jit(train_step, donate_argnums=(0, 1))

    def __call__(self, batch):
        params, opt_state = self.state
        params, opt_state, loss = self.step(
            params, opt_state, batch["tokens"], batch["segment_ids"],
            batch["positions"], batch["loss_mask"])
        self.state = (params, opt_state)
        return loss

    def close(self) -> None:
        self.state = None
