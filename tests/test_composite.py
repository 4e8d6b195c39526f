"""TM Composites on the normal serving path (Table III's four specialists
voting on one RGB frame), at a CPU size: 12x12x3 frames, specialists with
windows 3/4/12/5 at thermometer depths 3/4/1/1 (the last booleanized by
the adaptive Gaussian threshold), 40 clauses, literal budget 16 and
10-bit weights.  The served per-specialist class sums must equal the
plain-jnp oracle ``kernels/ref.py:composite_infer_ref`` bit for bit."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
from repro.core.composites import CompositeConfig, CompositeModel, composite_vote
from repro.core.cotm import CoTMConfig, CoTMModel, init_boundary_model
from repro.core.patches import PatchSpec
from repro.data.pipeline import preprocess_for_serving
from repro.kernels.ref import composite_infer_ref, fused_infer_ref
from repro.serve import ServiceConfig, ServingEngine, ServingService, freeze
from repro.serve.engine import composite_step_jit
from repro.serve.mesh import make_serve_mesh

GEOMETRY = ((3, 3), (4, 4), (12, 1), (5, 1))     # (window, thermometer depth)
BOOLEANIZE = (
    {"method": "thermometer", "levels": 3},
    {"method": "thermometer", "levels": 4},
    {"method": "thermometer", "levels": 1},
    {"method": "adaptive", "block_size": 5, "c": 2.0},
)


def _config(clauses=40, weight_bits=10) -> CompositeConfig:
    return CompositeConfig(specialists=tuple(
        CoTMConfig(
            n_clauses=clauses, n_classes=10,
            patch=PatchSpec(image_x=12, image_y=12, window_x=w, window_y=w,
                            channels=3, therm_bits=u),
            max_included_literals=16, weight_bits=weight_bits,
        )
        for w, u in GEOMETRY
    ))


def _model(config: CompositeConfig, seed=0) -> CompositeModel:
    """Each clause includes 1 to 16 features in random polarity, every
    tenth clause is empty, weights beyond the 10-bit range included."""
    rng = np.random.default_rng(seed)
    members = []
    for c in config.specialists:
        o = c.n_literals // 2
        feat = np.zeros((c.n_clauses, o), bool)
        for j in range(c.n_clauses):
            if j % 10:
                feat[j, rng.choice(o, rng.integers(1, 17), replace=False)] = True
        pol = rng.random((c.n_clauses, o)) < 0.5
        include = np.concatenate([feat & pol, feat & ~pol], axis=1)
        members.append(CoTMModel(
            ta_state=jnp.asarray(np.where(include, 200, 50).astype(np.uint8)),
            weights=jnp.asarray(rng.integers(-600, 601, (c.n_classes, c.n_clauses),
                                             dtype=np.int32)),
        ))
    return CompositeModel(members=tuple(members))


def _frames(n, seed=1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    level = rng.integers(40, 216, (n, 1, 1, 3))
    return np.clip(level + rng.integers(-90, 91, (n, 12, 12, 3)), 0, 255).astype(np.uint8)


def _reference(model, config, frames):
    lits = [
        preprocess_for_serving(frames, c.patch, packed=False, **b)
        for c, b in zip(config.specialists, BOOLEANIZE)
    ]
    preds, sums, _ = composite_infer_ref(
        lits, [m.include for m in model.members], [m.weights for m in model.members],
        weight_bits=10,
    )
    return np.asarray(preds), np.asarray(sums)


def _engine(model=None, config=None, max_batch=8):
    config = config or _config()
    engine = ServingEngine(max_batch=max_batch)
    engine.register("cifar", model or _model(config), config, booleanize=BOOLEANIZE)
    return engine


@pytest.mark.parametrize("n", [1, 3, 8, 11])
def test_served_sums_equal_reference(n):
    """Requests of 1, 3 and 8 frames (buckets 1, 4, 8) and of 11 (two
    chunks): per-specialist sums exact, predictions the vote's."""
    config = _config()
    model = _model(config)
    engine = _engine(model, config)
    frames = _frames(n, seed=n)
    res = engine.classify("cifar", frames)
    preds, sums = _reference(model, config, frames)
    assert res.class_sums.shape == (n, 4, 10) and res.class_sums.dtype == np.int32
    np.testing.assert_array_equal(res.class_sums, sums)
    np.testing.assert_array_equal(res.predictions, preds)
    assert (np.abs(sums) > 127).any()          # the 10-bit range is in use
    assert engine.stats("cifar").bucket_hits == (
        {8: 1, 4: 1} if n == 11 else {engine.bucket_for(n): 1}
    )
    # Every member's check ran as the folded convolution, in every chunk.
    assert engine.stats("cifar").folded_checks == 4 * (2 if n == 11 else 1)


def test_bucket_256_composite_equals_reference():
    """A full 256-frame chunk through the folded composite step: every
    specialist's sums exact, four folded checks counted."""
    config = _config()
    model = _model(config, seed=7)
    engine = _engine(model, config, max_batch=256)
    frames = _frames(256, seed=7)
    res = engine.classify("cifar", frames)
    preds, sums = _reference(model, config, frames)
    np.testing.assert_array_equal(res.class_sums, sums)
    np.testing.assert_array_equal(res.predictions, preds)
    assert engine.stats("cifar").folded_checks == 4


def test_service_submit_equals_reference():
    config = _config()
    model = _model(config)
    engine = _engine(model, config)
    service = ServingService(engine, ServiceConfig(max_delay_us=500.0))
    batches = [_frames(n, seed=10 + n) for n in (1, 3, 2, 5)]

    async def run():
        await service.start()
        try:
            return await asyncio.gather(*(service.submit("cifar", b) for b in batches))
        finally:
            await service.stop(drain=True)

    for frames, res in zip(batches, asyncio.run(run())):
        preds, sums = _reference(model, config, frames)
        np.testing.assert_array_equal(res.class_sums, sums)
        np.testing.assert_array_equal(res.predictions, preds)


def test_freeze_clamps_10_bit_weights_at_511():
    cfg = _config().specialists[0]
    w = jnp.asarray(np.tile([-1000, -512, -511, 0, 511, 512, 1000, 3, -3, 7], (40, 1)).T,
                    jnp.int32)
    model = CoTMModel(ta_state=jnp.zeros((40, cfg.n_literals), jnp.uint8), weights=w)
    sv = freeze(model, cfg)
    assert sv.weights.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(sv.weights)[:, 0],
                                  [-511, -511, -511, 0, 511, 511, 511, 3, -3, 7])


def test_active_clauses_are_each_members_nonempty_count():
    config = _config()
    model = _model(config)
    engine = _engine(model, config)
    want = tuple(int((np.asarray(m.include) > 0).any(axis=1).sum()) for m in model.members)
    assert want == (36, 36, 36, 36)
    assert engine.stats("cifar").active_clauses == want
    assert engine.stats("cifar").as_dict()["active_clauses"] == list(want)
    other = _model(config, seed=5)
    other.members[2].ta_state = other.members[2].ta_state.at[:7].set(0)
    engine.swap("cifar", other, config)
    want_other = tuple(
        int((np.asarray(m.include) > 0).any(axis=1).sum()) for m in other.members)
    assert engine.stats("cifar").active_clauses == want_other != want
    frames = _frames(5, seed=3)
    np.testing.assert_array_equal(engine.classify("cifar", frames).class_sums,
                                  _reference(other, config, frames)[1])
    engine.rollback("cifar")
    assert engine.stats("cifar").active_clauses == want


def test_8_bit_servable_holds_int8_weights_and_the_same_sums():
    cfg = COTM_CONFIGS["convcotm-mnist"]
    assert cfg.weight_bits == 8
    rng = np.random.default_rng(3)
    ta = np.full((cfg.n_clauses, cfg.n_literals), 50, np.uint8)
    for j in range(cfg.n_clauses):              # three literals a clause: clauses fire
        ta[j, rng.choice(cfg.n_literals, 3, replace=False)] = 200
    model = CoTMModel(ta_state=jnp.asarray(ta), weights=jnp.asarray(
        rng.integers(-300, 301, (cfg.n_classes, cfg.n_clauses)), jnp.int32))
    engine = ServingEngine(max_batch=8)
    sv = engine.register("mnist", model, cfg,
                         booleanize_method=BOOLEANIZE_METHOD["convcotm-mnist"])
    assert sv.weights.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(sv.weights),
                                  np.clip(np.asarray(model.weights), -127, 127))
    frames = np.random.default_rng(4).integers(0, 256, (5, 28, 28), dtype=np.uint8)
    lits = preprocess_for_serving(frames, cfg.patch, method="threshold")
    want = fused_infer_ref(jnp.asarray(lits), sv.include_packed, sv.nonempty, sv.weights)
    got = engine.classify("mnist", frames).class_sums
    np.testing.assert_array_equal(got, np.asarray(want))
    assert np.abs(got).max() > 0
    assert engine.stats("mnist").active_clauses == (int(np.asarray(sv.nonempty).sum()),)


def test_register_on_a_mesh_is_refused():
    engine = ServingEngine(max_batch=8, mesh=make_serve_mesh(1, 1))
    with pytest.raises(ValueError, match="composite of 4 specialists.*ServeMesh"):
        engine.register("cifar", _model(_config()), _config(), booleanize=BOOLEANIZE)


def test_autotune_on_a_composite_is_refused():
    engine = _engine()
    with pytest.raises(ValueError, match="composite of 4 specialists.*not autotuned"):
        engine.autotune("cifar")
    with pytest.raises(ValueError, match="composite of 4 specialists.*not autotuned"):
        ServingEngine(autotune=True).register(
            "cifar", _model(_config()), _config(), booleanize=BOOLEANIZE)


def test_swap_to_another_geometry_is_refused():
    engine = _engine()
    wider = _config(clauses=48)
    with pytest.raises(ValueError, match="composite config mismatch"):
        engine.swap("cifar", _model(wider), wider)
    single = _config().specialists[0]
    with pytest.raises(ValueError, match="composite and a single bank"):
        engine.swap("cifar", init_boundary_model(jax.random.PRNGKey(0), single), single)


def test_composite_serves_raw_frames_only():
    engine = _engine()
    frames = _frames(2)
    with pytest.raises(ValueError, match="raw frames only"):
        engine.classify("cifar", frames, ingress="host")
    with pytest.raises(ValueError, match="raw frames only"):
        engine.warmup("cifar", forms=("literals",))
    with pytest.raises(ValueError, match=r"must be \[n, 12, 12, 3\]"):
        engine.classify("cifar", frames[..., 0])
    assert engine.warmup("cifar") == (1, 2, 4, 8)


def test_degraded_composite_serves_the_same_sums():
    """The circuit breaker's fallback rebuilds every member's ingress for
    the next path down (matmul -> dense) and serves the same sums."""
    config = _config()
    model = _model(config)
    engine = _engine(model, config)
    frames = _frames(6, seed=9)
    want = engine.classify("cifar", frames)
    assert engine.degrade_path("cifar") == "dense"
    assert [s.packed for s in engine.ingress_spec("cifar")] == [False] * 4
    got = engine.classify("cifar", frames)
    np.testing.assert_array_equal(got.class_sums, want.class_sums)
    np.testing.assert_array_equal(got.predictions, want.predictions)


def test_wide_weights_are_refused_on_int8_kernel_paths():
    with pytest.raises(ValueError, match="int8 weights only"):
        ServingEngine(max_batch=8).register(
            "cifar", _model(_config()), _config(), booleanize=BOOLEANIZE, path="fused")


def test_step_is_one_program_with_a_scope_per_specialist():
    config = _config()
    engine = _engine(config=config)
    sv = engine.servable("cifar")
    sv = dataclasses.replace(sv, version=None)
    raw = jnp.zeros((4, 12, 12, 3), jnp.uint8)
    hlo = composite_step_jit().lower(
        sv, raw, path_name="matmul", ingress=engine.ingress_spec("cifar"), params=(),
    ).as_text(debug_info=True)
    for k in range(4):
        assert f"specialist{k}/" in hlo
    engine.classify("cifar", _frames(11))
    assert engine.stats("cifar").compiles == 2          # buckets 8 and 4, once each


@pytest.mark.parametrize("sums,want", [
    ([[[3, 3, 0], [0, 0, 0]]], 0),                    # a tie goes to the lowest class
    ([[[10, 0, 0], [0, 0, 2]]], 0),                   # 1 + 0 against 0 + 1: tie
    ([[[10, 0, 9], [0, 0, 0]]], 0),                   # an all-zero specialist adds nothing
    ([[[100, 0, 90], [-1, 0, 1]]], 2),                # scale-free: 0.9 + 1 beats 1 - 1
])
def test_vote_normalises_each_specialist(sums, want):
    preds, votes = composite_vote(jnp.asarray(sums, jnp.int32))
    assert int(preds[0]) == want
    assert votes.dtype == jnp.float32 and votes.shape == (1, 3)
