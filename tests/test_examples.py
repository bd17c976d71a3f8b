"""Example smoke tests (reference: examples/mnist/tests, examples/imagenet/tests)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_hello_world_roundtrip(tmp_path, capsys):
    from examples.hello_world.generate_dataset import generate_hello_world_dataset
    from examples.hello_world.read_dataset import (columnar_hello_world,
                                                   jax_hello_world,
                                                   python_hello_world)

    url = str(tmp_path / "hw")
    generate_hello_world_dataset(url, rows_count=6)
    python_hello_world(url)
    columnar_hello_world(url)
    jax_hello_world(url)
    out = capsys.readouterr().out
    assert out.count("row id=") == 6
    assert "device batch: image1 (4, 128, 256, 3)" in out


def test_mnist_jax_learns(tmp_path):
    from examples.mnist.train_mnist_jax import generate_dataset, train

    url = str(tmp_path / "mnist")
    generate_dataset(url, rows=512)
    acc = train(url, epochs=2, batch_size=64, shuffling_queue_capacity=128)
    assert acc > 0.5  # synthetic digits are separable; random = 0.1


def test_mnist_torch_smoke(tmp_path):
    from examples.mnist.train_mnist_jax import generate_dataset
    from examples.mnist.train_mnist_torch import train

    url = str(tmp_path / "mnist_t")
    generate_dataset(url, rows=256)
    acc = train(url, epochs=1, batch_size=64)
    assert acc > 0.2


def test_imagenet_resnet_smoke(tmp_path):
    from examples.imagenet.train_resnet_tpu import generate_dataset, train

    url = str(tmp_path / "imagenet")
    generate_dataset(url, rows=16, side=64)
    m = train(url, steps=2, global_batch=8, side=64, num_classes=10,
              decode="host")
    assert m["samples_per_sec"] > 0
    assert 0.0 <= m["input_wait_pct"] <= 100.0
    assert m["diagnostics"]["delivered_batches"] >= m["steps"]
    # hybrid on-chip decode (the default) feeds the same training step
    m = train(url, steps=2, global_batch=8, side=64, num_classes=10,
              decode="device")
    assert m["samples_per_sec"] > 0


def test_imagenet_device_decode_refuses_host_fallback(tmp_path, monkeypatch):
    """decode='device' without the native image library is an error, never
    a silent switch to host decode."""
    import jax
    from jax.sharding import Mesh

    from examples.imagenet.train_resnet_tpu import generate_dataset, make_feed
    from petastorm_tpu.native import image as native_image

    url = str(tmp_path / "imagenet")
    generate_dataset(url, rows=8, side=32)
    monkeypatch.setattr(native_image, "available", lambda: False)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with pytest.raises(RuntimeError, match="native image library"):
        make_feed(url, mesh, 8, decode="device")


def test_long_context_smoke(tmp_path):
    from examples.long_context.train_ring_attention import (generate_dataset,
                                                            train)

    url = str(tmp_path / "seqs")
    generate_dataset(url, rows=16, seq_len=32, vocab=64)
    losses = train(url, steps=3, global_batch=4, seq_len=32, vocab=64,
                   heads=2, head_dim=8, data_par=2)
    assert all(np.isfinite(v) for v in losses)
    # the Ulysses variant trains on the same delivery (heads=4 divides seq=4)
    losses = train(url, steps=2, global_batch=4, seq_len=32, vocab=64,
                   heads=4, head_dim=8, data_par=2, strategy="ulysses")
    assert all(np.isfinite(v) for v in losses)


def test_preemption_example_exact_resume(tmp_path):
    from examples.preemption.train_with_preemption import (generate_dataset,
                                                           train)

    url = str(tmp_path / "ds")
    generate_dataset(url, rows=1024)
    seen_a, seen_b, loss = train(url, batch_size=16, preempt_at=2,
                                 verbose=False)
    assert seen_a + seen_b == 1024      # every row exactly once across runs
    assert seen_b > 0                   # the preemption really cut mid-epoch
    assert np.isfinite(loss)


def test_spark_converter_example(tmp_path, capsys):
    from examples.spark_converter.convert_and_feed import main

    main(cache_dir=str(tmp_path / "cache"), rows=32)
    out = capsys.readouterr().out
    assert "converted: 32 rows" in out
    assert "jax loader delivered 32 rows" in out
    assert "torch DataLoader delivered 32 rows" in out
    assert "fingerprint cache" in out


def test_imagenet_tfdata_comparator_smoke(tmp_path):
    """The north-star comparator path (--input tfdata): TFRecord build from
    the stored jpegs, tf.data feed with the background device-transfer
    thread, and the SAME train step - smoke-tested at tiny shapes so the
    A/B harness the bench runs on the chip is covered by the suite too."""
    pytest.importorskip("tensorflow")
    from examples.imagenet.train_resnet_tpu import generate_dataset, train

    url = str(tmp_path / "ds")
    generate_dataset(url, rows=32, side=32)
    m = train(url, steps=2, global_batch=8, side=32, num_classes=10,
              workers=1, prefetch=2, input_pipeline="tfdata")
    assert m["input"] == "tfdata"
    assert m["steps"] == 2
    assert m["samples_per_sec"] > 0
    assert np.isfinite(m["final_loss"])

    # scan mode over the SAME feed: K steps per dispatch
    m2 = train(url, steps=4, global_batch=8, side=32, num_classes=10,
               workers=1, prefetch=2, input_pipeline="tfdata", scan_steps=2)
    assert m2["scan_steps"] == 2 and m2["steps"] == 4
    assert np.isfinite(m2["final_loss"])
