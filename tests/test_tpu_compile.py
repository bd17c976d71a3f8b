"""The main path's kernels compile for a TPU v5e that is described, not
attached: the TPU compiler installed here refuses what the chip's would.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist worker
imports this file.  The tests compile the kernels themselves - code that
asks ``jax.default_backend()`` still sees the CPU here - so the TPU branch
of ``normalize_images`` is taken by calling ``_normalize_impl`` with
``use_pallas=True``.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

SIDE = 224
LENGTH = SIDE * SIDE * 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # such a compile is written to a persistent cache but cannot be read back
    # without a chip, so keep any configured cache out of these tests
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _normalize_compiled(images, sharding_of_vectors):
    from petastorm_tpu.ops.normalize import _normalize_impl

    vec = jax.ShapeDtypeStruct((1, LENGTH), jnp.float32,
                               sharding=sharding_of_vectors)
    fn = jax.jit(lambda x, s, b: _normalize_impl(x, s, b, jnp.bfloat16, True))
    return fn.lower(images, vec, vec).compile()


def test_pallas_normalize_one_chip(one_chip):
    images = jax.ShapeDtypeStruct((32, SIDE, SIDE, 3), jnp.uint8,
                                  sharding=one_chip)
    compiled = _normalize_compiled(images, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_normalize_sharded_batch_2x2(topo):
    """The batch sharded P("data") over four chips, as JaxDataLoader
    delivers it: the kernel runs per device over its own rows (shard_map),
    with no gather of the batch."""
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    images = jax.ShapeDtypeStruct((128, SIDE, SIDE, 3), jnp.uint8,
                                  sharding=NamedSharding(mesh, P("data")))
    compiled = _normalize_compiled(images, NamedSharding(mesh, P()))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
    # each device writes only its 32 rows of bf16 (plus layout padding),
    # not the 128 rows of the whole batch
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert 32 * LENGTH * 2 <= out_bytes < 64 * LENGTH * 2


def test_jpeg_decode_coefficients_420_batch(one_chip):
    from petastorm_tpu.ops.jpeg import decode_coefficients

    n, blocks = 32, SIDE // 8
    planes = (
        jax.ShapeDtypeStruct((n, blocks, blocks, 64), jnp.int16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, blocks // 2, blocks // 2, 64), jnp.int16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, blocks // 2, blocks // 2, 64), jnp.int16,
                             sharding=one_chip),
    )
    qtabs = jax.ShapeDtypeStruct((n, 3, 64), jnp.uint16, sharding=one_chip)
    lowered = decode_coefficients.lower(
        planes, qtabs, image_size=(SIDE, SIDE),
        sampling=((2, 2), (1, 1), (1, 1)))
    # the IDCT and colour matmuls keep f32 accuracy on the MXU; at the TPU's
    # default precision the decode drifts from the host decode
    assert "HIGHEST" in lowered.as_text()
    compiled = lowered.compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((n, SIDE, SIDE, 3), jnp.uint8)


def test_random_resized_crop_and_flip_batch(one_chip):
    from petastorm_tpu.ops import random_flip, random_resized_crop

    def augment(images, key):
        k1, k2 = jax.random.split(key)
        return random_flip(random_resized_crop(images, k1, (SIDE, SIDE)), k2)

    images = jax.ShapeDtypeStruct((32, SIDE, SIDE, 3), jnp.uint8,
                                  sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(augment).lower(images, key).compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((32, SIDE, SIDE, 3), jnp.uint8)


def _finish_canvas_lowered(one_chip, canvas, n):
    """The 'device-mixed' finish of one 4:2:0 group of ``n`` images in an
    ILSVRC ``canvas``, written into a 256-row 640x640 batch."""
    from petastorm_tpu.ops.jpeg import finish_canvas

    (ch, cw), sds = canvas, jax.ShapeDtypeStruct
    planes = (sds((n, ch // 8, cw // 8, 64), jnp.int16, sharding=one_chip),
              sds((n, ch // 16, cw // 16, 64), jnp.int16, sharding=one_chip),
              sds((n, ch // 16, cw // 16, 64), jnp.int16, sharding=one_chip))
    return finish_canvas.lower(
        sds((256, 640, 640, 3), jnp.uint8, sharding=one_chip), planes,
        sds((n, 3, 64), jnp.uint16, sharding=one_chip),
        sds((n, 2), jnp.int32, sharding=one_chip),
        sds((n,), jnp.int32, sharding=one_chip),
        canvas_hw=canvas, sampling=((2, 2), (1, 1), (1, 1)))


def test_jpeg_finish_canvas_420_group(one_chip):
    """The 'device-mixed' finish of one group of 32 images in the largest
    ILSVRC canvas (640x640, 4:2:0), written into a 256-row batch."""
    lowered = _finish_canvas_lowered(one_chip, (640, 640), 32)
    assert "HIGHEST" in lowered.as_text()
    compiled = lowered.compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((256, 640, 640, 3), jnp.uint8)
    # the batch is donated and written in place: no second copy of it
    assert compiled.memory_analysis().alias_size_in_bytes == 256 * 640 * 640 * 3


@pytest.mark.parametrize("canvas,n", [((384, 512), 32), ((512, 384), 32),
                                      ((512, 512), 32), ((640, 640), 32),
                                      ((384, 512), 8)])
def test_jpeg_finish_canvas_writes_whole_rows(one_chip, canvas, n):
    """Every ILSVRC canvas writes its group's rows of the batch whole, in
    place and with no loop: a scatter of part of each row compiles to a
    loop of one read-select-write per image."""
    compiled = _finish_canvas_lowered(one_chip, canvas, n).compile()
    assert "while" not in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes == 256 * 640 * 640 * 3


def test_random_resized_crop_in_extents_batch(one_chip):
    from petastorm_tpu.ops import random_resized_crop

    images = jax.ShapeDtypeStruct((256, 640, 640, 3), jnp.uint8,
                                  sharding=one_chip)
    extents = jax.ShapeDtypeStruct((256, 2), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(
        lambda x, k, e: random_resized_crop(x, k, (SIDE, SIDE), extents=e)
    ).lower(images, key, extents).compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((256, SIDE, SIDE, 3), jnp.uint8)
