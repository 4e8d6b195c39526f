"""Sharded serving across a device mesh (serve/mesh.py).

The acceptance contract: predictions AND class sums from a meshed engine
are bit-identical to the single-device engine — for raw, host-ingress and
preprocessed request forms, replicated and clause-sharded placements, and
under ``ServingService`` concurrent load.

Single-device-mesh cases run everywhere (tier-1).  Multi-device cases
need virtual CPU devices: they skip unless the process was started with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the dedicated CI
multidevice job does exactly that); ``test_sharded_serve_8dev_subprocess``
additionally covers the 1/2/8-device sweep from a plain tier-1 run via a
subprocess, marked slow.
"""

import asyncio
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.cotm import CoTMConfig, init_boundary_model
from repro.core.patches import PatchSpec
from repro.serve import (
    ServeMesh,
    ServiceConfig,
    ServingEngine,
    ServingService,
    make_serve_mesh,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# n_clauses divisible by 2/4/8 so every clause-sharded mesh splits evenly.
SPEC = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
CFG = CoTMConfig(n_clauses=40, n_classes=10, patch=SPEC)


def _model(seed=0):
    return init_boundary_model(jax.random.PRNGKey(seed), CFG)


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    side = CFG.patch.image_y
    return rng.integers(0, 256, (n, side, side)).astype(np.uint8)


def _reference(max_batch=32):
    engine = ServingEngine(max_batch=max_batch)
    engine.register("m", _model(), CFG)
    return engine


def _meshed(data, model=1, *, shard_clauses=None, max_batch=32):
    smesh = make_serve_mesh(data, model, shard_clauses=shard_clauses)
    engine = ServingEngine(max_batch=max_batch, mesh=smesh)
    engine.register("m", _model(), CFG)
    return engine, smesh


def _need_devices(n):
    if jax.device_count() < n:
        pytest.skip(
            f"needs {n} devices; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )


def _assert_identical(meshed: ServingEngine, ref: ServingEngine, n=13, seed=3):
    """All three request forms bit-identical between two engines."""
    imgs = _images(n, seed=seed)
    want = ref.classify("m", imgs)
    for kw in (dict(), dict(ingress="host")):
        got = meshed.classify("m", imgs, **kw)
        np.testing.assert_array_equal(want.predictions, got.predictions)
        np.testing.assert_array_equal(want.class_sums, got.class_sums)
    lits = meshed.preprocess("m", imgs)
    got = meshed.classify("m", lits, preprocessed=True)
    np.testing.assert_array_equal(want.predictions, got.predictions)
    np.testing.assert_array_equal(want.class_sums, got.class_sums)


class TestServeMeshPlacement:
    def test_requires_data_axis(self):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("a", "b"))
        with pytest.raises(ValueError, match='"data" axis'):
            ServeMesh(mesh)

    def test_clause_sharding_requires_model_axis(self):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        with pytest.raises(ValueError, match='"model" axis'):
            ServeMesh(mesh, shard_clauses=True)

    def test_clause_count_must_divide(self):
        _need_devices(2)
        smesh = make_serve_mesh(1, 2, shard_clauses=True)
        cfg = CoTMConfig(n_clauses=7, n_classes=3, patch=SPEC)  # 7 % 2 != 0
        with pytest.raises(ValueError, match="does not divide"):
            ServingEngine(max_batch=8, mesh=smesh).register(
                "m", init_boundary_model(jax.random.PRNGKey(0), cfg), cfg
            )

    def test_data_axis_must_be_pow2_and_fit_max_batch(self):
        from jax.sharding import Mesh

        smesh = make_serve_mesh(1, 1)
        ServingEngine(max_batch=1, mesh=smesh)  # 1 divides everything
        _need_devices(3)
        mesh3 = Mesh(np.array(jax.devices()[:3]).reshape(3, 1), ("data", "model"))
        with pytest.raises(ValueError, match="power of two"):
            ServingEngine(max_batch=8, mesh=ServeMesh(mesh3))
        with pytest.raises(ValueError, match="exceeds max_batch"):
            ServingEngine(max_batch=1, mesh=make_serve_mesh(2, 1))

    def test_bucket_clamped_to_data_shards(self):
        engine, smesh = _meshed(1)
        assert engine.bucket_for(1) == 1
        assert engine.bucket_for(3) == 4
        _need_devices(4)
        engine, smesh = _meshed(4)
        assert engine.data_shards == 4
        assert engine.bucket_for(1) == 4     # smallest shardable bucket
        assert engine.bucket_for(3) == 4
        assert engine.bucket_for(5) == 8

    def test_batch_placed_across_all_devices(self):
        """The dispatched buffer's rows really land on every mesh device
        (the 'batch work placed across all devices' acceptance check)."""
        _need_devices(8)
        engine, smesh = _meshed(8)
        x = smesh.place_batch(_images(16))
        devices_used = {s.device for s in x.addressable_shards}
        assert len(devices_used) == 8
        assert all(s.data.shape[0] == 2 for s in x.addressable_shards)

    def test_stats_carry_mesh_geometry(self):
        engine, _ = _meshed(1)
        engine.classify("m", _images(5))
        st = engine.stats("m")
        assert st.devices == 1 and st.data_shards == 1
        d = st.as_dict()
        assert d["devices"] == 1
        assert d["per_device_bucket_hits"] == {8: 1}

    def test_per_device_bucket_accounting(self):
        _need_devices(8)
        engine, _ = _meshed(8, max_batch=64)
        engine.classify("m", _images(16))
        engine.classify("m", _images(3))     # bucket 4 -> clamped to 8
        st = engine.stats("m")
        assert st.devices == 8
        assert st.bucket_hits == {16: 1, 8: 1}
        assert st.per_device_bucket_hits == {2: 1, 1: 1}


class TestShardedBitIdentity:
    """Predictions/class sums identical across device counts and forms."""

    def test_single_device_mesh_replicated(self):
        engine, _ = _meshed(1)
        _assert_identical(engine, _reference())

    def test_single_device_mesh_clause_sharded(self):
        # n_model == 1 still exercises the full shard_map + psum path.
        engine, _ = _meshed(1, 1, shard_clauses=True)
        _assert_identical(engine, _reference())

    def test_two_device_data_parallel(self):
        _need_devices(2)
        engine, _ = _meshed(2)
        _assert_identical(engine, _reference())

    def test_eight_device_data_parallel(self):
        _need_devices(8)
        engine, _ = _meshed(8)
        _assert_identical(engine, _reference())

    def test_clause_sharded_four_way(self):
        _need_devices(4)
        engine, _ = _meshed(1, 4)
        _assert_identical(engine, _reference())

    def test_data_and_clause_sharded(self):
        _need_devices(8)
        engine, _ = _meshed(2, 4)
        _assert_identical(engine, _reference())

    def test_warmup_compiles_both_forms_meshed(self):
        engine, _ = _meshed(1)
        compiled = engine.warmup("m", buckets=[2, 8])
        assert compiled == (2, 8)
        st = engine.stats("m")
        assert st.requests == 0              # warmup never pollutes stats

    @pytest.mark.parametrize("path", ["dense", "bitpacked", "matmul"])
    def test_clause_sharded_across_paths(self, path):
        """The shard_map program wraps every registered eval path."""
        ref = ServingEngine(max_batch=32)
        ref.register("m", _model(), CFG, path=path)
        smesh = make_serve_mesh(1, 1, shard_clauses=True)
        eng = ServingEngine(max_batch=32, mesh=smesh)
        eng.register("m", _model(), CFG, path=path)
        imgs = _images(9, seed=7)
        want = ref.classify("m", imgs)
        got = eng.classify("m", imgs)
        np.testing.assert_array_equal(want.predictions, got.predictions)
        np.testing.assert_array_equal(want.class_sums, got.class_sums)

    @pytest.mark.parametrize("path", ["sparse", "fused_sparse", "matmul_sparse"])
    @pytest.mark.parametrize(
        "geometry", [(1, 1, False), (2, 1, False), (1, 2, True), (2, 2, True)],
        ids=["replicated", "data2", "clause2", "data2xclause2"],
    )
    def test_sparse_paths_on_mesh(self, path, geometry):
        """Sparse paths stay bit-identical under ServeMesh sharding:
        replicated placement serves the real sparse kernels (the analysis
        replicates with the model), clause-sharded placement drops the
        analysis and resolves to the dense fallback inside the shard_map
        — either way results equal the unmeshed dense engine."""
        data, model_ax, shard = geometry
        _need_devices(data * model_ax)
        ref = ServingEngine(max_batch=32)
        ref.register("m", _model(), CFG, path="dense")
        eng2 = ServingEngine(max_batch=32, mesh=make_serve_mesh(
            data, model_ax, shard_clauses=shard))
        eng2.register("m", _model(), CFG, path=path)
        assert (eng2.servable("m").sparsity is None) == shard
        for n in (1, 5, 9):
            imgs = _images(n, seed=n)
            want = ref.classify("m", imgs)
            for kw in ({"ingress": "device"}, {"ingress": "host"}):
                got = eng2.classify("m", imgs, **kw)
                np.testing.assert_array_equal(want.predictions, got.predictions)
                np.testing.assert_array_equal(want.class_sums, got.class_sums)


class TestServiceOnMesh:
    def _run_service_load(self, engine, ref, max_coalesce=None):
        service = ServingService(
            engine,
            ServiceConfig(max_delay_us=500.0, max_coalesce=max_coalesce),
        )

        async def run():
            await service.start()
            sizes = [1, 3, 7, 2, 5, 1, 4, 6, 2, 1]
            batches = [_images(n, seed=10 + i) for i, n in enumerate(sizes)]

            async def one(b, i):
                await asyncio.sleep(0.0005 * (i % 3))
                return await service.submit("m", b)

            results = await asyncio.gather(
                *(one(b, i) for i, b in enumerate(batches))
            )
            await service.stop(drain=True)
            return batches, results

        batches, results = asyncio.run(run())
        for b, r in zip(batches, results):
            want = ref.classify("m", b)
            np.testing.assert_array_equal(r.predictions, want.predictions)
            np.testing.assert_array_equal(r.class_sums, want.class_sums)

    def test_service_bit_identical_single_device_mesh(self):
        engine, _ = _meshed(1)
        self._run_service_load(engine, _reference())

    def test_service_bit_identical_multidevice(self):
        _need_devices(8)
        engine, _ = _meshed(8)
        self._run_service_load(engine, _reference())

    def test_service_bit_identical_clause_sharded(self):
        _need_devices(4)
        engine, _ = _meshed(2, 2)
        self._run_service_load(engine, _reference())

    def test_max_coalesce_scales_with_data_shards(self):
        _need_devices(4)
        engine, _ = _meshed(4)
        service = ServingService(engine, ServiceConfig(max_coalesce=8))
        assert service._sched.max_coalesce == 32   # 8 images per shard
        plain = ServingService(_reference(), ServiceConfig(max_coalesce=8))
        assert plain._sched.max_coalesce == 8

    def test_max_coalesce_scaling_clamped_to_max_batch(self):
        """The scaled window never exceeds the largest bucket: one
        microbatch must stay one dispatch, not a chain of max_batch
        slices."""
        _need_devices(8)
        engine, _ = _meshed(8, max_batch=32)
        service = ServingService(engine, ServiceConfig(max_coalesce=8))
        assert service._sched.max_coalesce == 32   # min(64, max_batch)
        # unmeshed legacy behavior: an explicit oversized window survives
        big = ServingService(
            _reference(max_batch=16), ServiceConfig(max_coalesce=64)
        )
        assert big._sched.max_coalesce == 64


def test_copy_back_started_at_launch_4x1_subprocess():
    """On a virtual-CPU 4x1 data mesh, the meshed engine is bit-identical
    to the single-device engine in both request forms, one chunk and
    three, and every output's host copy began at launch and was read
    once (subprocess: the device count is set before jax initializes)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, numpy as np
from repro.core.cotm import CoTMConfig, init_boundary_model
from repro.core.patches import PatchSpec
from repro.serve import ServingEngine, make_serve_mesh

spec = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
cfg = CoTMConfig(n_clauses=40, n_classes=10, patch=spec)
model = init_boundary_model(jax.random.PRNGKey(0), cfg)
ref = ServingEngine(max_batch=8)
ref.register("m", model, cfg)
eng = ServingEngine(max_batch=8, mesh=make_serve_mesh(4, 1))
eng.register("m", model, cfg)
assert eng.devices == 4 and eng.data_shards == 4

reads = 0
for n in (1, 2 * 8 + 3):
    imgs = np.random.default_rng(n).integers(0, 256, (n, 11, 11)).astype(np.uint8)
    for preprocessed in (False, True):
        x = eng.preprocess("m", imgs) if preprocessed else imgs
        want = ref.classify("m", x, preprocessed=preprocessed)
        handle = eng.dispatch("m", x, preprocessed=preprocessed)
        got = handle.result()
        np.testing.assert_array_equal(want.predictions, got.predictions)
        np.testing.assert_array_equal(want.class_sums, got.class_sums)
        assert handle.result() is got
        reads += 2 * -(-n // 8)
        st = eng.stats("m")
        assert st.copies_started == st.copies_read == reads, st.as_dict()
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=300, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_folded_check_on_clause_sharded_1x2_subprocess():
    """On a virtual-CPU 1x2 clause-sharded mesh the matmul path's raw
    form derives its folded kernel and constant from each shard's own
    clauses: class sums equal the single-device dense path, and every
    chunk counts as a folded check (subprocess: the device count is set
    before jax initializes)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.core.cotm import CoTMConfig, CoTMModel
from repro.core.patches import PatchSpec
from repro.serve import ServingEngine, make_serve_mesh

for spec, method, kw in (
    (PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5), "threshold", {}),
    (PatchSpec(image_x=12, image_y=12, window_x=4, window_y=4, channels=3,
               therm_bits=3), "thermometer", {"levels": 3}),
):
    cfg = CoTMConfig(n_clauses=40, n_classes=10, patch=spec)
    rng = np.random.default_rng(0)
    ta = np.full((40, cfg.n_literals), 50, np.uint8)
    for j in range(1, 40):                    # clause 0 stays empty
        ta[j, rng.choice(cfg.n_literals, rng.integers(1, 5), replace=False)] = 200
    model = CoTMModel(ta_state=jnp.asarray(ta), weights=jnp.asarray(
        rng.integers(-127, 128, (10, 40)), jnp.int32))
    ref = ServingEngine(max_batch=8)
    ref.register("m", model, cfg, path="dense", booleanize_method=method,
                 booleanize_kw=kw)
    eng = ServingEngine(max_batch=8, mesh=make_serve_mesh(1, 2))
    eng.register("m", model, cfg, path="matmul", booleanize_method=method,
                 booleanize_kw=kw)
    assert eng.devices == 2 and eng.mesh.shard_clauses
    shape = (11, spec.image_y, spec.image_x) + ((3,) if spec.channels > 1 else ())
    imgs = np.random.default_rng(5).integers(0, 256, shape).astype(np.uint8)
    want, got = ref.classify("m", imgs), eng.classify("m", imgs)
    np.testing.assert_array_equal(want.class_sums, got.class_sums)
    np.testing.assert_array_equal(want.predictions, got.predictions)
    assert np.abs(got.class_sums).max() > 0
    assert eng.stats("m").folded_checks == 2, eng.stats("m").as_dict()
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=300, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_sharded_serve_8dev_subprocess():
    """The full 1/2/8-device bit-identity sweep from a plain run: the
    device count must be set before jax initializes, so it runs in a
    subprocess (covers tier-1 environments with a single device)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, numpy as np
from repro.core.cotm import CoTMConfig, init_boundary_model
from repro.core.patches import PatchSpec
from repro.serve import ServingEngine, make_serve_mesh

spec = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
cfg = CoTMConfig(n_clauses=40, n_classes=10, patch=spec)
model = init_boundary_model(jax.random.PRNGKey(0), cfg)
imgs = np.random.default_rng(0).integers(0, 256, (13, 11, 11)).astype(np.uint8)

ref = ServingEngine(max_batch=32)
ref.register("m", model, cfg)
want = ref.classify("m", imgs)

for data, mdl, sc in ((1, 1, False), (2, 1, False), (8, 1, False),
                      (1, 4, True), (2, 4, True)):
    eng = ServingEngine(
        max_batch=32, mesh=make_serve_mesh(data, mdl, shard_clauses=sc)
    )
    eng.register("m", model, cfg)
    for kw in ({}, {"ingress": "host"}):
        got = eng.classify("m", imgs, **kw)
        np.testing.assert_array_equal(want.predictions, got.predictions)
        np.testing.assert_array_equal(want.class_sums, got.class_sums)
    lits = eng.preprocess("m", imgs)
    got = eng.classify("m", lits, preprocessed=True)
    np.testing.assert_array_equal(want.class_sums, got.class_sums)
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=540, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
