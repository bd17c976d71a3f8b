"""Both consumer steps compile for a described TPU v5e at the cells'
real shapes, and fit its memory: ResNet-50 at 256 images on one chip and
1,024 over a 2x2 mesh, GPT-2 small at 12 x 1,024 tokens.  The topology is
described inside a fixture, never at import.  ``normalize_images`` picks
the Pallas kernel by ``jax.default_backend()``, which sees the CPU here,
so the test steers that one call."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.consumers import gpt2, resnet50

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
HBM = 16e9


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compile(build, config, batch, mesh, inputs, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    init, step = build(config, batch)
    rep = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        *state, *inputs).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{build.__module__} batch {batch} on {mesh.size} chip(s):"
          f" arguments {mem.argument_size_in_bytes}, temporaries"
          f" {mem.temp_size_in_bytes} bytes per chip")
    return compiled, used


@pytest.mark.parametrize("chips", [1, 4])
def test_resnet50_step_compiles_and_fits(topo, chips, monkeypatch):
    config = _config("imagenet_rn50")
    batch = config["batch_per_chip"] * chips
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    data = NamedSharding(mesh, P("data"))
    side = config["data"]["side"]
    inputs = (jax.ShapeDtypeStruct((batch, side, side, 3), jnp.uint8,
                                   sharding=data),
              jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=data),
              jax.ShapeDtypeStruct((2,), jnp.uint32,
                                   sharding=NamedSharding(mesh, P())))
    compiled, used = _compile(resnet50.build, config, batch, mesh, inputs,
                              monkeypatch)
    assert "tpu_custom_call" in compiled.as_text()
    assert used < HBM


def test_gpt2_step_compiles_and_fits(topo, monkeypatch):
    config = _config("tokens_gpt2")
    batch, t = config["batch_per_chip"], config["model"]["block_size"]
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    data = NamedSharding(mesh, P("data"))
    inputs = tuple(jax.ShapeDtypeStruct((batch, t), dt, sharding=data)
                   for dt in (jnp.int32, jnp.int32, jnp.int32, jnp.float32))
    _, used = _compile(gpt2.build, config, batch, mesh, inputs, monkeypatch)
    assert used < HBM
