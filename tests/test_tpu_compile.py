"""Compile the Pallas kernels and the fused serving step for a TPU v5e.

Nothing here runs: each case lowers and compiles for a described (not
attached) ``v5e:2x2`` chip with the TPU compiler that ships with JAX,
which refuses what the chip would refuse — primitives Mosaic cannot
lower, layouts it cannot cast, VMEM over the scoped limit — none of
which the interpret-mode tests can see.  Shapes are the paper geometry
(28x28 frames, 10x10 window: P=361 patches, W=9 words, C=128 clauses,
M=10 classes) at bucket 8 (buckets 1-8 pad to ``block_b=8``) and at
``max_batch`` 256.  Each compiled executable must contain a
``tpu_custom_call``: the kernel compiled, it did not fall back.

The topology is described inside fixtures, never at import time: only
one process may load the TPU library, and every xdist worker imports
this file.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.convcotm import COTM_CONFIGS
from repro.core.cotm import init_boundary_model
from repro.core.ingress import IngressSpec
from repro.kernels import ops
from repro.serve.engine import raw_step_jit
from repro.serve.servable import freeze

ARCH = "convcotm-mnist"
BUCKETS = (8, 256)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off:
    an entry compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_case(name, b, sds):
    """(fn, abstract args) for one ops.py kernel at paper geometry."""
    spec = COTM_CONFIGS[ARCH].patch
    p, w, c, m = spec.n_patches, spec.n_words, 128, 10
    lits = sds((b, p, w), jnp.uint32)
    masks = sds((c, w), jnp.uint32)
    weights = sds((m, c), jnp.int32)
    nonempty = sds((c,), jnp.uint8)
    pallas = dict(backend="pallas")
    return {
        "ingress_pack": (
            lambda x: ops.ingress_pack(x, spec, **pallas),
            (sds((b, spec.image_y, spec.image_x), jnp.uint8),),
        ),
        "clause_eval": (
            lambda l, i, n: ops.clause_eval(l, i, n, **pallas),
            (lits, masks, nonempty),
        ),
        "class_sum": (
            lambda f, wt: ops.class_sum(f, wt, **pallas),
            (sds((b, c), jnp.uint8), weights),
        ),
        "fused_infer": (
            lambda l, i, n, wt: ops.fused_infer(l, i, n, wt, **pallas),
            (lits, masks, nonempty, weights),
        ),
        "clause_eval_sparse": (
            lambda l, e: ops.clause_eval_sparse(l, e, **pallas),
            (lits, masks),
        ),
        "fused_infer_sparse": (
            lambda l, e, wt: ops.fused_infer_sparse(l, e, wt, **pallas),
            (lits, masks, weights),
        ),
    }[name]


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize(
    "kernel",
    ["ingress_pack", "clause_eval", "class_sum", "fused_infer",
     "clause_eval_sparse", "fused_infer_sparse"],
)
def test_kernel_compiles_for_v5e(one_chip, kernel, bucket):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_case(kernel, bucket, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bucket", BUCKETS)
def test_fused_raw_step_compiles_for_v5e(one_chip, bucket):
    """The engine's raw-form step on the ``fused`` path: ingress kernel
    into the fused clause-eval + class-sum kernel, one executable.  The
    test process's default backend is the CPU, so the step is told to
    use Pallas: through the IngressSpec for the ingress, and through the
    path parameters for evaluation."""
    cfg = dataclasses.replace(COTM_CONFIGS[ARCH], eval_path="fused")
    servable = freeze(init_boundary_model(jax.random.PRNGKey(0), cfg), cfg)
    on_chip = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        servable,
    )
    spec = IngressSpec(patch=cfg.patch, method="threshold", kernel_backend="pallas")
    raw = jax.ShapeDtypeStruct(
        (bucket, cfg.patch.image_y, cfg.patch.image_x), jnp.uint8, sharding=one_chip
    )
    compiled = raw_step_jit().lower(
        on_chip, raw, path_name="fused", ingress=spec,
        params=(("backend", "pallas"),),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_composite_step_compiles_for_v5e(one_chip):
    """The Table III composite's step at its published widths (four
    1000-clause specialists on 32x32x3 frames, 10-bit weights) on the
    ``matmul`` path, bucket 8: the int16 class-sum operands and the
    HIGHEST-precision adaptive booleanization compile for the chip."""
    from repro.configs.convcotm import CIFAR10_COMPOSITES, COMPOSITE_BOOLEANIZE
    from repro.core.composites import CompositeModel
    from repro.core.cotm import CoTMModel
    from repro.serve.engine import composite_step_jit
    from repro.serve.paths import get_path
    from repro.serve.servable import freeze_composite

    comp = CIFAR10_COMPOSITES
    model = CompositeModel(members=tuple(
        CoTMModel(ta_state=jax.ShapeDtypeStruct((c.n_clauses, c.n_literals), jnp.uint8),
                  weights=jax.ShapeDtypeStruct((c.n_classes, c.n_clauses), jnp.int32))
        for c in comp.specialists))
    servable = jax.eval_shape(lambda m: freeze_composite(m, comp), model)
    on_chip = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), servable
    )
    path = get_path("matmul")
    ingress = tuple(
        path.ingress_spec(c.patch, **b)
        for c, b in zip(comp.specialists, COMPOSITE_BOOLEANIZE["cifar10-composites"])
    )
    raw = jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.uint8, sharding=one_chip)
    compiled = composite_step_jit().lower(
        on_chip, raw, path_name="matmul", ingress=ingress, params=()
    ).compile()
    assert "s16[10,1000]" in compiled.as_text()


def test_composite_step_folds_its_clause_checks_for_v5e(one_chip):
    """The composite step at bucket 256 on the ``matmul`` path checks
    every specialist's clauses as the folded convolution
    (``core.clauses.eval_clauses_folded``): the executable holds a
    convolution under ``specialist<k>/clause_conv`` for each of the four
    and no patch-window gather, and its temporaries stay under the
    58,499,072 bytes the gathered form compiled to at this bucket."""
    import re

    from repro.configs.convcotm import CIFAR10_COMPOSITES, COMPOSITE_BOOLEANIZE
    from repro.core.composites import CompositeModel
    from repro.core.cotm import CoTMModel
    from repro.serve.engine import composite_step_jit
    from repro.serve.paths import get_path
    from repro.serve.servable import freeze_composite

    comp = CIFAR10_COMPOSITES
    model = CompositeModel(members=tuple(
        CoTMModel(ta_state=jax.ShapeDtypeStruct((c.n_clauses, c.n_literals), jnp.uint8),
                  weights=jax.ShapeDtypeStruct((c.n_classes, c.n_clauses), jnp.int32))
        for c in comp.specialists))
    servable = jax.eval_shape(lambda m: freeze_composite(m, comp), model)
    on_chip = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), servable
    )
    path = get_path("matmul")
    ingress = tuple(
        path.ingress_spec(c.patch, **b)
        for c, b in zip(comp.specialists, COMPOSITE_BOOLEANIZE["cifar10-composites"])
    )
    raw = jax.ShapeDtypeStruct((256, 32, 32, 3), jnp.uint8, sharding=one_chip)
    compiled = composite_step_jit().lower(
        on_chip, raw, path_name="matmul", ingress=ingress, params=()
    ).compile()
    text = compiled.as_text()
    convs = [ln for ln in text.splitlines() if " convolution(" in ln]
    for k in range(len(comp.specialists)):
        assert any(f"specialist{k}/clause_conv/" in ln for ln in convs), k
    assert not re.search(r" gather\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 58_499_072
