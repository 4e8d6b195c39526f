"""Each cell's step program compiles for a described TPU v5e (``v5e:2x2``)
at the cell's buckets: the raw classify step on its registered eval path
for one chip, and the 4x1 data-sharded meshed step for four.  Nothing
runs; the TPU compiler refuses here what it would refuse on the chip."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

import harness
import system


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _served(cfg):
    from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
    from repro.core.cotm import CoTMModel
    from repro.serve.paths import get_path
    from repro.serve.servable import freeze

    arch = cfg["arch"]
    pcfg = COTM_CONFIGS[arch]
    ta, w = system.make_model_arrays(jax, cfg, 1)
    servable = dataclasses.replace(freeze(CoTMModel(ta_state=ta, weights=w), pcfg),
                                   version=None)
    spec = get_path(pcfg.eval_path).ingress_spec(pcfg.patch,
                                                 method=BOOLEANIZE_METHOD[arch])
    return servable, spec, pcfg.eval_path


@pytest.mark.parametrize("bucket", [1, 16, 256])
def test_one_chip_step_compiles(topo, bucket):
    from repro.serve.engine import raw_step_jit

    cfg = harness.load_config(harness.load_spec(), "convcotm-mnist")
    servable, spec, path = _served(cfg)
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
                           servable)
    raw = jax.ShapeDtypeStruct((bucket, 28, 28), jnp.uint8, sharding=one)
    compiled = raw_step_jit().lower(on_chip, raw, path_name=path, ingress=spec,
                                    params=()).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_mesh4x1_step_compiles(topo):
    from repro.serve.mesh import ServeMesh, classify_step_meshed

    cfg = harness.load_config(harness.load_spec(), "convcotm-mnist")
    servable, spec, path = _served(cfg)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())
    on_mesh = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
                           servable)
    raw = jax.ShapeDtypeStruct((256, 28, 28), jnp.uint8,
                               sharding=NamedSharding(mesh, PartitionSpec("data")))
    compiled = classify_step_meshed.lower(on_mesh, raw, smesh=ServeMesh(mesh),
                                          path_name=path, ingress=spec, params=()).compile()
    assert "all-reduce" not in compiled.as_text()      # data-sharded: no collective
