"""Tests for on-device ops and consumer models (CPU backend; pallas via interpret)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu.ops.normalize import _choose_block, normalize_images


def test_normalize_xla_path_matches_numpy():
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 255, (4, 8, 8, 3), dtype=np.uint8))
    mean, std = (0.5, 0.4, 0.3), (0.2, 0.25, 0.3)
    out = np.asarray(normalize_images(imgs, mean, std, out_dtype=jnp.float32))
    want = (np.asarray(imgs, np.float32) / 255.0 - np.array(mean, np.float32)) \
        / np.array(std, np.float32)
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_normalize_pallas_kernel_interpret_matches():
    # run the actual pallas kernel in interpret mode on CPU
    from petastorm_tpu.ops import normalize as nz

    rng = np.random.default_rng(1)
    n, h, w, c = 8, 16, 8, 3  # L = 16*8*3 = 384 -> 128-divisible
    imgs = rng.integers(0, 255, (n, h, w, c), dtype=np.uint8)
    length = h * w * c
    std = np.array((0.2, 0.25, 0.3), np.float32)
    mean = np.array((0.5, 0.4, 0.3), np.float32)
    scale = np.tile(1.0 / (255.0 * std), length // c)[None, :]
    bias = np.tile(-mean / std, length // c)[None, :]
    block = _choose_block(n, length)
    assert block is not None

    from jax.experimental import pallas as pl

    out = pl.pallas_call(
        nz._normalize_kernel,
        out_shape=jax.ShapeDtypeStruct((n, length), jnp.float32),
        grid=(n // block[0], length // block[1]),
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j)),
                  pl.BlockSpec((1, block[1]), lambda i, j: (0, j)),
                  pl.BlockSpec((1, block[1]), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        interpret=True,
    )(imgs.reshape(n, length), jnp.asarray(scale), jnp.asarray(bias))
    want = (imgs.reshape(n, length).astype(np.float32) / 255.0
            - np.tile(mean, length // c)) / np.tile(std, length // c)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)


@pytest.mark.parametrize("mesh_shape,axes,batch_axis", [
    ((8,), ("data",), "data"),                    # the loader's default axis
    ((4, 2), ("data", "model"), "data"),          # rows replicated over model
    ((2, 4), ("model", "data"), "data"),          # batch not on the first axis
])
def test_normalize_pallas_sharded_matches_xla(monkeypatch, mesh_shape, axes,
                                              batch_axis):
    """The kernel path over a sharded batch (shard_map, one block of rows per
    device) gives the XLA expression's values, with the rows staying put;
    the kernel runs in interpret mode so the CPU can take the TPU branch.
    A batch that is not on the first mesh axis is resharded around the
    kernel, with the same result."""
    from functools import partial

    from jax.experimental import pallas as pl
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.ops import normalize as nz

    monkeypatch.setattr(pl, "pallas_call",
                        partial(pl.pallas_call, interpret=True))
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(mesh_shape), axes)
    batch = P(batch_axis)
    rng = np.random.default_rng(2)
    imgs = jax.device_put(rng.integers(0, 255, (64, 8, 16, 3), dtype=np.uint8),
                          NamedSharding(mesh, batch))
    length = 8 * 16 * 3
    scale = jnp.asarray(rng.random((1, length), np.float32))
    bias = jnp.asarray(rng.random((1, length), np.float32))
    out = nz._normalize_impl(imgs, scale, bias, jnp.float32, True)
    want = nz._normalize_impl(imgs, scale, bias, jnp.float32, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    if batch_axis == axes[0]:
        assert out.sharding.is_equivalent_to(NamedSharding(mesh, batch), 4)
    lowered = jax.jit(partial(nz._normalize_impl, out_dtype=jnp.float32,
                              use_pallas=True)
                      ).lower(imgs, scale, bias).as_text()
    assert "shard_map" in lowered or "sdy.manual_computation" in lowered


def test_normalize_rejects_bad_inputs():
    with pytest.raises(TypeError):
        normalize_images(jnp.zeros((2, 4, 4, 3), jnp.float32))
    with pytest.raises(ValueError):
        normalize_images(jnp.zeros((2, 4, 4, 3), jnp.uint8), mean=(0.5, 0.5))


def test_choose_block_constraints():
    assert _choose_block(8, 1024) is not None
    assert _choose_block(7, 1024) is None     # rows not 8-divisible
    assert _choose_block(8, 100) is None      # cols not 128-divisible


def test_mlp_forward():
    from petastorm_tpu.models import MLP

    model = MLP(features=(16,), num_classes=10)
    x = jnp.zeros((4, 28, 28), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(params, x)
    assert out.shape == (4, 10)


def test_resnet_tiny_forward():
    # tiny stage config to keep CPU compile fast; exercises the block wiring
    from petastorm_tpu.models.resnet import ResNet

    model = ResNet(stage_sizes=[1, 1], num_classes=7, num_filters=8,
                   dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(params, x)
    assert out.shape == (2, 7)


def test_graft_entry_shapes():
    # entry() must return (jittable fn, example args) - trace without executing
    import sys
    sys.path.insert(0, "/root/repo")
    from __graft_entry__ import entry

    fn, args = entry()
    shape = jax.eval_shape(fn, *args)
    assert shape.shape == (8, 1000)


def test_random_crop_flip_augmentation():
    """On-device batched augmentation: correct geometry, per-image
    randomness, deterministic per key, pixels preserved (no interpolation)."""
    from petastorm_tpu.ops import random_crop, random_crop_flip, random_flip

    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 255, (16, 12, 10, 3), dtype=np.uint8))
    key = jax.random.PRNGKey(7)

    crops = random_crop(imgs, key, (8, 6))
    assert crops.shape == (16, 8, 6, 3) and crops.dtype == jnp.uint8
    # every crop is a contiguous window of its source image
    src = np.asarray(imgs)
    for i, c in enumerate(np.asarray(crops)):
        found = any(np.array_equal(src[i, y:y + 8, x:x + 6], c)
                    for y in range(5) for x in range(5))
        assert found, i
    # distinct offsets across the batch (overwhelmingly likely)
    assert len({c.tobytes() for c in np.asarray(crops)}) > 1

    flipped = random_flip(imgs, key)
    f = np.asarray(flipped)
    states = {True: 0, False: 0}
    for i in range(16):
        if np.array_equal(f[i], src[i]):
            states[False] += 1
        else:
            assert np.array_equal(f[i], src[i, :, ::-1])
            states[True] += 1
    assert states[True] > 0 and states[False] > 0  # both outcomes occur

    both = random_crop_flip(imgs, key, crop_hw=(8, 6))
    assert both.shape == (16, 8, 6, 3)
    # deterministic per key, and the key actually drives the outcome
    assert np.array_equal(np.asarray(both),
                          np.asarray(random_crop_flip(imgs, key, crop_hw=(8, 6))))
    other = random_crop_flip(imgs, jax.random.PRNGKey(8), crop_hw=(8, 6))
    assert not np.array_equal(np.asarray(both), np.asarray(other))

    with pytest.raises(ValueError, match="larger than"):
        random_crop(imgs, key, (20, 6))


# -- resize / random-resized-crop (on-chip ImageNet preprocessing) ------------


def test_resize_images_uint8_roundtrip_and_identity():
    import jax.numpy as jnp

    from petastorm_tpu.ops import resize_images

    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, (3, 32, 48, 3), dtype=np.uint8)
    out = resize_images(jnp.asarray(imgs), (16, 24))
    assert out.shape == (3, 16, 24, 3) and out.dtype == jnp.uint8
    # same-size resize is (near-)identity
    same = np.asarray(resize_images(jnp.asarray(imgs), (32, 48)))
    assert np.abs(same.astype(int) - imgs.astype(int)).max() <= 1
    # constant image stays constant through antialiased resampling
    flat = np.full((1, 32, 48, 3), 77, np.uint8)
    out_flat = np.asarray(resize_images(jnp.asarray(flat), (20, 20)))
    assert np.abs(out_flat.astype(int) - 77).max() <= 1


def test_random_resized_crop_static_shapes_and_determinism():
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import random_resized_crop

    rng = np.random.default_rng(1)
    imgs = jnp.asarray(rng.integers(0, 255, (4, 40, 56, 3), dtype=np.uint8))
    key = jax.random.PRNGKey(3)
    a = random_resized_crop(imgs, key, (24, 24))
    assert a.shape == (4, 24, 24, 3) and a.dtype == jnp.uint8
    b = random_resized_crop(imgs, key, (24, 24))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # same key
    c = random_resized_crop(imgs, jax.random.PRNGKey(4), (24, 24))
    assert not np.array_equal(np.asarray(a), np.asarray(c))      # new key


def test_random_resized_crop_full_scale_equals_resize():
    """scale=(1,1), ratio=(1,1) on a square image pins the crop to the whole
    frame: the op must agree with a plain antialiased resize."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import random_resized_crop, resize_images

    rng = np.random.default_rng(2)
    imgs = jnp.asarray(rng.integers(0, 255, (2, 32, 32, 3), dtype=np.uint8))
    got = np.asarray(random_resized_crop(imgs, jax.random.PRNGKey(0), (16, 16),
                                         scale=(1.0, 1.0), ratio=(1.0, 1.0),
                                         antialias=True))
    want = np.asarray(resize_images(imgs, (16, 16)))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


def test_random_resized_crop_constant_image_constant_output():
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import random_resized_crop

    flat = jnp.full((3, 48, 48, 3), 130, jnp.uint8)
    out = np.asarray(random_resized_crop(flat, jax.random.PRNGKey(9), (20, 20)))
    assert np.abs(out.astype(int) - 130).max() <= 1


def test_mixup_blend_and_label_pairing():
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import mixup

    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.standard_normal((8, 8, 8, 3)).astype(np.float32))
    labels = jnp.arange(8)
    key = jax.random.PRNGKey(1)
    mixed, la, lb, lam = mixup(imgs, labels, key, alpha=0.4)
    lam_f = float(lam)
    assert 0.5 <= lam_f <= 1.0  # dominant-first convention
    # la is the original labels; lb is a permutation of them
    np.testing.assert_array_equal(np.asarray(la), np.arange(8))
    assert sorted(np.asarray(lb).tolist()) == list(range(8))
    # the blend is exactly lam*a + (1-lam)*b for the paired images
    b_idx = np.asarray(lb)  # the permutation used
    want = lam_f * np.asarray(imgs) + (1 - lam_f) * np.asarray(imgs)[b_idx]
    np.testing.assert_allclose(np.asarray(mixed), want, rtol=1e-5, atol=1e-5)
    # deterministic per key
    mixed2, _, _, _ = mixup(imgs, labels, key, alpha=0.4)
    np.testing.assert_array_equal(np.asarray(mixed), np.asarray(mixed2))


def test_mixup_uint8_roundtrip():
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import mixup

    imgs = jnp.asarray(np.random.default_rng(1).integers(
        0, 255, (4, 6, 6, 3), dtype=np.uint8))
    mixed, _, _, _ = mixup(imgs, jnp.arange(4), jax.random.PRNGKey(0))
    assert mixed.dtype == jnp.uint8 and mixed.shape == imgs.shape


def test_cutmix_box_area_matches_lambda():
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import cutmix

    n, h, w = 6, 32, 32
    imgs = jnp.asarray(np.random.default_rng(2).integers(
        0, 255, (n, h, w, 3), dtype=np.uint8))
    labels = jnp.arange(n)
    mixed, la, lb, lam = cutmix(imgs, labels, jax.random.PRNGKey(7))
    assert mixed.dtype == jnp.uint8
    perm = np.asarray(lb)
    src, dst = np.asarray(imgs), np.asarray(mixed)
    # count pixels equal to the partner but not to self (unambiguous on
    # random uint8 content): that fraction is the pasted box = 1 - lam.
    # Fixed points of the permutation (partner IS self) carry no signal -
    # exclude those images from the measurement entirely.
    moved = perm != np.arange(n)
    assert moved.any()
    partner = src[perm]
    in_box = ((dst == partner).all(axis=-1)
              & ~(partner == src).all(axis=-1))[moved]
    frac = in_box.sum() / in_box.size
    assert abs((1 - float(lam)) - frac) < 0.05
    np.testing.assert_array_equal(np.asarray(la), np.arange(n))
    assert sorted(perm.tolist()) == list(range(n))
