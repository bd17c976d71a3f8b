"""The ILSVRC consumer step compiles for a described TPU v5e at the cell's
real shapes and fits its memory: ResNet-50 over 256 canvases of 640x640
with their extents, cropped inside them to 224.  As ``test_compile.py``:
the topology is described inside a fixture, and ``normalize_images`` is
steered to its TPU branch for the one call."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.consumers import resnet50_ilsvrc

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
TRAFFIC = os.path.join(os.path.dirname(CONFIGS), "traffic")
HBM = 16e9


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


def test_ilsvrc_step_compiles_and_fits(topo, monkeypatch):
    with open(os.path.join(CONFIGS, "ilsvrc_rn50.json")) as f:
        config = json.load(f)
    with open(os.path.join(TRAFFIC, "ilsvrc_rrc.json")) as f:
        canvas = max(tuple(c) for c in
                     json.load(f)["loader"]["pad_shapes"]["image"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    batch = config["batch_per_chip"]
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    data = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    init, step = resnet50_ilsvrc.build(config, batch)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    inputs = (jax.ShapeDtypeStruct((batch,) + canvas, jnp.uint8,
                                   sharding=data),
              jax.ShapeDtypeStruct((batch, 2), jnp.int32, sharding=data),
              jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=data),
              jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        *state, *inputs).compile()
    mem = compiled.memory_analysis()
    print(f"ilsvrc step: arguments {mem.argument_size_in_bytes},"
          f" temporaries {mem.temp_size_in_bytes} bytes")
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
