"""The ILSVRC cell's consumer: the image cells' ResNet-50 v1.5 step
(``resnet50.ResNet``, bfloat16, SGD with momentum 0.9), fed canvases of
uint8 images and each image's extent.

The on-chip transform is MLPerf's: a random-resized crop drawn inside each
image's own extent (``ops.random_resized_crop(..., extents=)``), a flip,
and the Pallas normalize.  For the check the step returns, on a few rows,
the crop boxes (``ops.crop_boxes``, the same key), the crops, the flipped
crops and the normalized values, and on more rows the canvases as
delivered.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import petastorm_tpu.ops as ops
from benchmarks.consumers.resnet50 import ResNet, check_rows


def sample_rows(batch: int) -> np.ndarray:
    """Rows of each step whose delivered canvases the step returns for the
    pixel check: 32 spread over the batch (all of a smaller one)."""
    return np.unique(np.linspace(0, batch - 1, min(batch, 32))
                     .round().astype(np.int32))


def build(config: dict, batch: int):
    """(init, train_step): ``init(key)`` makes (params, opt_state);
    ``train_step(params, opt_state, image_u8, extent, label, key)``."""
    m = config["model"]
    if config["batch_norm"] != "running_average":
        raise ValueError("the step normalizes with frozen running statistics"
                         " only, as the example it copies")
    side = m["input_side"]
    model = ResNet(stage_sizes=m["stage_sizes"], num_classes=m["num_classes"])
    tx = optax.sgd(m["learning_rate"], momentum=m["momentum"])
    rows = check_rows(batch)
    samples = sample_rows(batch)
    num_classes = m["num_classes"]

    def init(key):
        params = model.init(key, jnp.zeros((1, side, side, 3), jnp.bfloat16))
        return params, tx.init(params)

    def train_step(params, opt_state, image_u8, extent, label, key):
        def loss_fn(p):
            k1, k2 = jax.random.split(key)
            crops = ops.random_resized_crop(image_u8, k1, (side, side),
                                            extents=extent)
            imgs = ops.random_flip(crops, k2)
            x = ops.normalize_images(imgs)
            logits = model.apply(p, x)
            onehot = jax.nn.one_hot(label, num_classes)
            loss = -(jax.nn.log_softmax(logits) * onehot).sum(-1).mean()
            boxes = ops.crop_boxes(k1, extent)
            return loss, (boxes[rows], crops[rows], imgs[rows], x[rows],
                          image_u8[samples])

        (loss, seen), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, seen

    return init, train_step


class Consumer:
    """As ``resnet50.Consumer``, with each image's extent beside the batch;
    ``step`` is the jitted train step (``jit_train_step`` in the trace)."""

    def __init__(self, config: dict, mesh, batch: int, seed: int):
        init, train_step = build(config, batch)
        self.state = jax.jit(init, out_shardings=NamedSharding(mesh, P()))(
            jax.random.PRNGKey(seed))
        self.step = jax.jit(train_step, donate_argnums=(0, 1))

    def __call__(self, image_u8, extent, label, key):
        params, opt_state = self.state
        params, opt_state, loss, seen = self.step(params, opt_state, image_u8,
                                                  extent, label, key)
        self.state = (params, opt_state)
        return loss, seen

    def close(self) -> None:
        self.state = None
