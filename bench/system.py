"""The served model and the frames, made from ``--seed`` by the benchmark's
own code, and the system under test, built from the program's public
pieces the way ``launch/serve.py`` builds it.

The model is made here rather than by the program's
``init_boundary_model``: that initialiser includes about half of all 272
literals in every clause, so no clause can ever fire and every class sum
is 0, which no comparison could tell from a broken datapath.  The model
made here has the shape of a trained ConvCoTM: each clause includes a
few features, each in one polarity, some clauses are empty, and the int8
weights span their whole range.  Its distribution is stated in the
configuration file (``model``).
"""

from __future__ import annotations

import numpy as np

from harness import say


def key_seed(seed: int) -> int:
    """A 32-bit PRNG seed from any whole ``--seed`` (which may pass 2**31)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def make_model_arrays(jax, cfg: dict, seed: int):
    """(ta_state uint8 [C, 2o], weights int32 [m, C]) on the device, in one
    jitted call from the seed."""
    import jax.numpy as jnp

    c, m = cfg["n_clauses"], cfg["n_classes"]
    o = cfg["n_literals"] // 2
    lo, hi = cfg["model"]["include_share"]
    empty_share = cfg["model"]["empty_share"]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 7)
        rate = jax.random.uniform(ks[0], (c, 1), minval=lo, maxval=hi)
        empty = jax.random.uniform(ks[1], (c, 1)) < empty_share
        rate = jnp.where(empty, 0.0, rate)
        feat = jax.random.uniform(ks[2], (c, o)) < rate
        pol = jax.random.bernoulli(ks[3], 0.5, (c, o))
        include = jnp.concatenate([feat & pol, feat & ~pol], axis=1)
        ta = jnp.where(
            include,
            jax.random.randint(ks[4], (c, 2 * o), 128, 256),
            jax.random.randint(ks[5], (c, 2 * o), 0, 128),
        ).astype(jnp.uint8)
        weights = jax.random.randint(ks[6], (m, c), -127, 128).astype(jnp.int32)
        return ta, weights

    return make(jax.random.PRNGKey(key_seed(seed)))


def make_frames(rng: np.random.Generator, n: int, h: int, w: int) -> np.ndarray:
    """uint8 [n, h, w] frames with structure: a coarse random
    field (one value per 4x4 block) plus pixel noise, so both the fixed
    and the adaptive booleanization see edges and flat areas."""
    coarse = rng.integers(0, 256, (n, -(-h // 4), -(-w // 4)), dtype=np.int16)
    field = np.repeat(np.repeat(coarse, 4, axis=1), 4, axis=2)[:, :h, :w]
    noise = rng.integers(-48, 49, (n, h, w), dtype=np.int16)
    return np.clip(field + noise, 0, 255).astype(np.uint8)


def check_config(cfg: dict, pcfg, method: str, ingress) -> None:
    """The configuration file must describe what the program serves."""
    p = pcfg.patch
    served = {
        "image_y": p.image_y, "image_x": p.image_x,
        "window_y": p.window_y, "window_x": p.window_x,
        "stride_y": p.stride_y, "stride_x": p.stride_x,
        "n_patches": p.n_patches, "n_literals": p.n_literals,
        "n_clauses": pcfg.n_clauses, "n_classes": pcfg.n_classes,
    }
    for k, v in served.items():
        if cfg[k] != v:
            raise ValueError(f"{cfg['arch']}: config file {k}={cfg[k]}, program serves {v}")
    b = cfg["booleanize"]
    if b["method"] != method:
        raise ValueError(f"{cfg['arch']}: booleanize {b['method']!r}, program {method!r}")
    for k, v in b.items():
        if k != "method" and getattr(ingress, k) != v:
            raise ValueError(f"{cfg['arch']}: booleanize {k}={v}, program {getattr(ingress, k)}")


def build_engine(cfg: dict, traffic: dict, ta, weights):
    """A ``ServingEngine`` with defaults, the model registered under its
    arch with the arch's booleanization and registered eval path."""
    from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
    from repro.core.cotm import CoTMModel
    from repro.serve import ServingEngine
    from repro.serve.mesh import make_serve_mesh

    arch = cfg["arch"]
    pcfg, method = COTM_CONFIGS[arch], BOOLEANIZE_METHOD[arch]
    mesh = None
    if traffic.get("mesh"):
        data, model = (int(x) for x in traffic["mesh"].split("x"))
        mesh = make_serve_mesh(data, model)
    engine = ServingEngine(mesh=mesh)
    engine.register(arch, CoTMModel(ta_state=ta, weights=weights), pcfg,
                    booleanize_method=method)
    check_config(cfg, pcfg, method, engine.ingress_spec(arch))
    say(f"system: {arch} eval_path={pcfg.eval_path} max_batch={engine.max_batch} "
        f"devices={engine.devices} data_shards={engine.data_shards}")
    return engine
