"""The image cells' consumer: one ResNet-50 v1.5 training step in bfloat16
with SGD and momentum 0.9, fed uint8 images.

A fixed copy of ``examples/imagenet/train_resnet_tpu.py``'s
``make_train_step`` and of ``petastorm_tpu/models/resnet.py``, kept here so
that no change to the program can speed the model up and call it an
ingest gain.  The on-chip transform stays a call into the program
(``petastorm_tpu.ops``): random-resized crop, flip, and the Pallas
normalize, which the check reads back on a few rows of every step.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import petastorm_tpu.ops as ops


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: Any
    norm: Any
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=True, momentum=0.9,
                       epsilon=1e-5, dtype=self.dtype)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                 name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(self.num_filters * 2 ** i, strides, conv,
                                    norm, nn.relu)(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


def check_rows(batch: int) -> np.ndarray:
    """Rows of each step whose cropped pixels and normalized values the
    step returns for the check: eight, spread over the batch so that every
    chip's shard holds some."""
    return np.unique(np.linspace(0, batch - 1, 8).round().astype(np.int32))


def build(config: dict, batch: int):
    """(init, train_step): ``init(key)`` makes (params, opt_state) from a
    key; ``train_step`` is the step, to be jitted."""
    m = config["model"]
    if config["batch_norm"] != "running_average":
        raise ValueError("the step normalizes with frozen running statistics"
                         " only, as the example it copies")
    side = config["data"]["side"]
    model = ResNet(stage_sizes=m["stage_sizes"], num_classes=m["num_classes"])
    tx = optax.sgd(m["learning_rate"], momentum=m["momentum"])
    rows = check_rows(batch)
    num_classes = m["num_classes"]

    def init(key):
        params = model.init(key, jnp.zeros((1, side, side, 3), jnp.bfloat16))
        return params, tx.init(params)

    def train_step(params, opt_state, image_u8, label, key):
        def loss_fn(p):
            k1, k2 = jax.random.split(key)
            imgs = ops.random_resized_crop(image_u8, k1, (side, side))
            imgs = ops.random_flip(imgs, k2)
            x = ops.normalize_images(imgs)
            logits = model.apply(p, x)
            onehot = jax.nn.one_hot(label, num_classes)
            loss = -(jax.nn.log_softmax(logits) * onehot).sum(-1).mean()
            return loss, (imgs[rows], x[rows])

        (loss, seen), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, seen

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

    def __call__(self, image_u8, label, key):
        params, opt_state = self.state
        params, opt_state, loss, seen = self.step(params, opt_state,
                                                  image_u8, label, key)
        self.state = (params, opt_state)
        return loss, seen

    def close(self) -> None:
        self.state = None
