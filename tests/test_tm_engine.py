"""TrainerEngine: jitted-epoch training vs the naive per-batch loop.

The contract under test: with the same starting key, cursor and batch
order, ``TrainerEngine`` (literals frozen once, one lax.scan per epoch,
donated model buffers, matmul training eval) produces the *bit-identical*
model to a hand-written ``update_batch`` python loop over ``batches()`` —
so "same accuracy as the naive epoch loop" holds by construction and is
asserted directly on the glyphs example config.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cotm import CoTMConfig, init_model
from repro.core.patches import PatchSpec
from repro.core.train import update_batch
from repro.data import PipelineState, batches, booleanize_split, synthetic_glyphs
from repro.train.tm_engine import TMDataset, TrainerEngine

SPEC_SMALL = PatchSpec(image_x=8, image_y=8, window_x=3, window_y=3)


def _small_cfg(**kw):
    base = dict(n_clauses=16, n_classes=3, patch=SPEC_SMALL, T=15, s=3.0)
    base.update(kw)
    return CoTMConfig(**base)


def _small_data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = (rng.random((n, 8, 8)) > 0.5).astype(np.uint8)
    y = rng.integers(0, 3, n).astype(np.int32)
    return x, y


def _naive_loop(cfg, key, x, y, batch, epochs, mode="batch", seed=0):
    """The hand-written epoch loop the engine must reproduce bit-exactly."""
    model = init_model(key, cfg)
    state = PipelineState(seed=seed)
    for _ in range(epochs):
        for xb, yb, state in batches(x, y, batch, state):
            key, k = jax.random.split(key)
            model = update_batch(
                k, model, jnp.asarray(xb), jnp.asarray(yb), cfg, mode=mode
            )
    return model, state


class TestEngineEquivalence:
    @pytest.mark.parametrize("mode", ["batch", "scan"])
    def test_engine_matches_naive_loop_bitexact(self, mode):
        cfg = _small_cfg()
        x, y = _small_data()
        key = jax.random.PRNGKey(3)
        want, want_state = _naive_loop(cfg, key, x, y, batch=16, epochs=2, mode=mode)

        engine = TrainerEngine(cfg, batch_size=16, mode=mode)
        ds = engine.prepare(x, y, booleanize_method="none")
        model = engine.init_model(key)
        _, model, state, _ = engine.fit(key, model, ds, epochs=2)
        np.testing.assert_array_equal(
            np.asarray(want.ta_state), np.asarray(model.ta_state)
        )
        np.testing.assert_array_equal(
            np.asarray(want.weights), np.asarray(model.weights)
        )
        assert state == want_state

    def test_resume_mid_epoch_matches_full_epoch(self):
        """run_epoch from a mid-epoch cursor trains exactly the remaining
        steps of that epoch's permutation."""
        cfg = _small_cfg()
        x, y = _small_data()
        key = jax.random.PRNGKey(1)
        engine = TrainerEngine(cfg, batch_size=16)
        ds = engine.prepare(x, y, booleanize_method="none")

        model = engine.init_model(key)
        key_a, model_full, state_full, n_full = engine.run_epoch(
            key, model, ds, PipelineState(seed=5)
        )
        assert n_full == 64 and state_full == PipelineState(1, 0, 5)

        # Same epoch in two halves: 2 steps, then resume from the cursor.
        model2 = engine.init_model(key)
        k = key
        state = PipelineState(seed=5)
        from repro.data import epoch_permutation

        perm = epoch_permutation(5, 0, 64)
        for step in range(2):
            k, kk = jax.random.split(k)
            idx = perm[step * 16 : (step + 1) * 16]
            model2 = update_batch(
                kk, model2, jnp.asarray(x[idx]), jnp.asarray(y[idx]), cfg
            )
        key_b, model2, state2, n2 = engine.run_epoch(
            k, model2, ds, PipelineState(epoch=0, step=2, seed=5)
        )
        assert n2 == 32 and state2 == PipelineState(1, 0, 5)
        np.testing.assert_array_equal(
            np.asarray(model_full.ta_state), np.asarray(model2.ta_state)
        )
        np.testing.assert_array_equal(
            np.asarray(model_full.weights), np.asarray(model2.weights)
        )
        np.testing.assert_array_equal(np.asarray(key_a), np.asarray(key_b))

    def test_exhausted_cursor_rolls_over_and_trains(self):
        """A cursor exhausted on entry (step == n_steps, e.g. a pre-fix
        checkpoint) rolls forward and trains the next epoch — bit-identical
        to what the naive batches() loop does with the same stale cursor."""
        cfg = _small_cfg()
        x, y = _small_data()
        key = jax.random.PRNGKey(0)
        engine = TrainerEngine(cfg, batch_size=16)
        ds = engine.prepare(x, y, booleanize_method="none")
        model = engine.init_model(key)
        stale = PipelineState(epoch=2, step=4, seed=0)
        key_e, model_e, state, n = engine.run_epoch(key, model, ds, stale)
        assert n == 64 and state == PipelineState(4, 0, 0)

        model_n = engine.init_model(key)
        k = key
        st = stale
        for xb, yb, st in batches(x, y, 16, stale):
            k, kk = jax.random.split(k)
            model_n = update_batch(kk, model_n, jnp.asarray(xb), jnp.asarray(yb), cfg)
        assert st == state
        np.testing.assert_array_equal(
            np.asarray(model_n.ta_state), np.asarray(model_e.ta_state)
        )
        np.testing.assert_array_equal(np.asarray(k), np.asarray(key_e))

    @pytest.mark.slow
    def test_glyphs_engine_accuracy_matches_naive(self):
        """The glyphs example config (paper geometry, 128 clauses): the
        engine reaches exactly the naive loop's accuracy — the models are
        bit-identical — and actually learns."""
        tx, ty, vx, vy = synthetic_glyphs(n_train=1000, n_test=300, seed=1)
        txb = booleanize_split(tx, "threshold")
        vxb = booleanize_split(vx, "threshold")
        cfg = CoTMConfig(n_clauses=128, n_classes=10, T=100, s=5.0)
        key = jax.random.PRNGKey(0)

        engine = TrainerEngine(cfg, batch_size=100)
        train_ds = engine.prepare(txb, ty, booleanize_method="none")
        eval_ds = engine.prepare(vxb, vy, booleanize_method="none")
        model_e = engine.init_model(key)
        _, model_e, _, reports = engine.fit(
            key, model_e, train_ds, epochs=5, eval_ds=eval_ds
        )
        acc_engine = reports[-1].accuracy

        model_n, _ = _naive_loop(
            cfg, key, txb, ty.astype(np.int32), batch=100, epochs=5
        )
        np.testing.assert_array_equal(
            np.asarray(model_n.ta_state), np.asarray(model_e.ta_state)
        )
        acc_naive = engine.evaluate(model_n, eval_ds)
        assert acc_engine == acc_naive
        assert acc_engine >= 0.75, f"glyph accuracy {acc_engine}"


class TestEngineAPI:
    def test_prepare_runs_shared_ingress(self):
        """prepare() must produce exactly the pipeline ingress literals."""
        from repro.data.pipeline import preprocess_for_serving

        cfg = _small_cfg()
        x, y = _small_data(n=8)
        engine = TrainerEngine(cfg, batch_size=4)
        ds = engine.prepare(x, y, booleanize_method="none")
        want = preprocess_for_serving(x, cfg.patch, method="none", packed=False)
        assert isinstance(ds, TMDataset)
        assert ds.n == 8
        np.testing.assert_array_equal(np.asarray(ds.literals), want)
        np.testing.assert_array_equal(np.asarray(ds.labels), y)

    def test_evaluate_matches_accuracy(self):
        from repro.core.train import accuracy

        cfg = _small_cfg()
        x, y = _small_data(n=16, seed=4)
        # eval_batch=5 forces the chunked path incl. a remainder chunk
        engine = TrainerEngine(cfg, batch_size=4, eval_batch=5)
        ds = engine.prepare(x, y, booleanize_method="none")
        model = engine.init_model(jax.random.PRNGKey(2))
        want = float(accuracy(model, jnp.asarray(x), jnp.asarray(y), cfg))
        assert engine.evaluate(model, ds) == want

    def test_dataset_smaller_than_batch_rejected(self):
        """A dataset with fewer samples than batch_size must raise, not
        silently train 0 samples while advancing the epoch cursor."""
        cfg = _small_cfg()
        x, y = _small_data(n=8)
        engine = TrainerEngine(cfg, batch_size=16)
        ds = engine.prepare(x, y, booleanize_method="none")
        model = engine.init_model(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="batch_size"):
            engine.run_epoch(jax.random.PRNGKey(0), model, ds)

    def test_invalid_modes_rejected(self):
        cfg = _small_cfg()
        with pytest.raises(ValueError, match="mode"):
            TrainerEngine(cfg, mode="nope")
        with pytest.raises(ValueError, match="batch_size"):
            TrainerEngine(cfg, batch_size=0)

    def test_scan_mode_with_mesh_rejected(self):
        from jax.sharding import Mesh

        cfg = _small_cfg()
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        with pytest.raises(ValueError, match="sequential"):
            TrainerEngine(cfg, mode="scan", mesh=mesh)

    def test_single_device_mesh_matches_unmeshed(self):
        """The shard_map psum path on a 1-device mesh is bit-identical to
        the plain sum (the multi-device contract, minus the devices — the
        8-device version runs in the slow suite)."""
        from jax.sharding import Mesh

        cfg = _small_cfg()
        x, y = _small_data(n=32, seed=9)
        key = jax.random.PRNGKey(11)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

        plain = TrainerEngine(cfg, batch_size=16)
        meshed = TrainerEngine(cfg, batch_size=16, mesh=mesh)
        ds_a = plain.prepare(x, y, booleanize_method="none")
        ds_b = meshed.prepare(x, y, booleanize_method="none")
        m_a = plain.init_model(key)
        m_b = meshed.init_model(key)
        _, m_a, _, _ = plain.fit(key, m_a, ds_a, epochs=1)
        _, m_b, _, _ = meshed.fit(key, m_b, ds_b, epochs=1)
        np.testing.assert_array_equal(
            np.asarray(m_a.ta_state), np.asarray(m_b.ta_state)
        )
        np.testing.assert_array_equal(
            np.asarray(m_a.weights), np.asarray(m_b.weights)
        )


@pytest.mark.slow
def test_tm_engine_data_parallel_8dev():
    """TrainerEngine with a mesh: per-device delta sums combined by the
    shard_map psum must give a model bit-identical to the unmeshed run
    (subprocess: the device count must be set before jax initializes)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core.cotm import CoTMConfig
from repro.core.patches import PatchSpec
from repro.train.tm_engine import TrainerEngine

spec = PatchSpec(image_x=8, image_y=8, window_x=3, window_y=3)
cfg = CoTMConfig(n_clauses=16, n_classes=3, patch=spec, T=15, s=3.0)
rng = np.random.default_rng(0)
x = (rng.random((64, 8, 8)) > 0.5).astype(np.uint8)
y = rng.integers(0, 3, 64).astype(np.int32)
key = jax.random.PRNGKey(2)

plain = TrainerEngine(cfg, batch_size=16)
ds = plain.prepare(x, y, booleanize_method="none")
m1 = plain.init_model(key)
_, m1, _, _ = plain.fit(key, m1, ds, epochs=2)

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
meshed = TrainerEngine(cfg, batch_size=16, mesh=mesh)
ds2 = meshed.prepare(x, y, booleanize_method="none")
m2 = meshed.init_model(key)
_, m2, _, _ = meshed.fit(key, m2, ds2, epochs=2)

np.testing.assert_array_equal(np.asarray(m1.ta_state), np.asarray(m2.ta_state))
np.testing.assert_array_equal(np.asarray(m1.weights), np.asarray(m2.weights))
print("OK")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=repo, timeout=540, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


class TestEvaluateHostSyncContract:
    """Regression pins for the tmlint TM103 fix: evaluate() used to
    int() every chunk inside the dispatch loop, serializing chunk k+1's
    dispatch behind chunk k's compute."""

    def _engine_and_ds(self, n=23, eval_batch=7):
        cfg = _small_cfg()
        x, y = _small_data(n=n)
        engine = TrainerEngine(cfg, batch_size=8, eval_batch=eval_batch)
        ds = engine.prepare(x, y, booleanize_method="none")
        return engine, ds

    def test_single_host_conversion_per_evaluate(self):
        """Exactly ONE int() conversion for the whole split, however many
        chunks it evaluates (here ceil(23/7) = 4 chunks)."""
        engine, ds = self._engine_and_ds()
        model = engine.init_model(jax.random.PRNGKey(0))
        conversions = {"n": 0}
        real_eval = engine._eval_fn

        class Spy:
            def __init__(self, v):
                self.v = v

            def __add__(self, other):
                return Spy(self.v + (other.v if isinstance(other, Spy) else other))

            __radd__ = __add__

            def __int__(self):
                conversions["n"] += 1
                return int(self.v)

        engine._eval_fn = lambda *a: Spy(real_eval(*a))
        acc = engine.evaluate(model, ds)
        assert conversions["n"] == 1
        assert 0.0 <= acc <= 1.0

    def test_chunked_evaluate_bitexact_vs_single_dispatch(self):
        """Chunking (and the deferred conversion) never changes the
        result: same accuracy as one whole-dataset dispatch."""
        engine, ds = self._engine_and_ds()
        model = engine.init_model(jax.random.PRNGKey(1))
        acc = engine.evaluate(model, ds)
        whole = int(engine._eval_fn(model, ds.literals, ds.labels))
        assert acc == whole / ds.n


class TestTrainerNoRecompile:
    def test_steady_state_epochs_do_not_recompile(self):
        """After the first epoch + evaluate compile, further same-shape
        epochs and evals reuse the caches (tools/recompile_guard)."""
        from tools.recompile_guard import no_recompiles

        cfg = _small_cfg()
        x, y = _small_data(n=64)
        engine = TrainerEngine(cfg, batch_size=16, eval_batch=32)
        ds = engine.prepare(x, y, booleanize_method="none")
        key = jax.random.PRNGKey(5)
        model = engine.init_model(key)
        # warm both executables: one epoch + one eval (full chunk shape)
        key, model, state, _ = engine.run_epoch(key, model, ds)
        engine.evaluate(model, ds)
        with no_recompiles((engine, "_epoch_fn"), (engine, "_eval_fn")):
            for _ in range(2):
                key, model, state, _ = engine.run_epoch(key, model, ds, state)
                engine.evaluate(model, ds)
