"""The hooks of the test configuration ``toy-rgb``: 12x12 frames of three
channels, a 4x4 window at stride 2, 40 clauses of 4 to 9 literals each
and 4 classes; its own model pytree (a dict), frames, engine, work count,
step names and a NumPy reference of its own.  The reference imports
nothing of the program and follows ``repro.core.patches``' literal order:
the window's pixels row-major, each pixel's channels in order, then the
y- and x-position thermometers of the patch; the literals are the
features and then their negations."""

from __future__ import annotations

import numpy as np

STEP_MODULES = ("jit__classify_raw_step",)


def make_model(jax, cfg, seed):
    """{"ta": uint8 [C, 2o], "weights": int32 [m, C]}: each clause includes
    a few features, each in one polarity; the first clauses are empty."""
    import jax.numpy as jnp

    c, m, o = cfg["n_clauses"], cfg["n_classes"], cfg["n_literals"] // 2
    lo, hi = cfg["model"]["included_literals"]
    empty = cfg["model"]["empty_clauses"]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 4)
        count = jax.random.randint(ks[0], (c, 1), lo, hi + 1)
        count = jnp.where(jnp.arange(c)[:, None] < empty, 0, count)
        rank = jnp.argsort(jax.random.uniform(ks[1], (c, o)), axis=1)
        feat = rank < count
        pol = jax.random.bernoulli(ks[2], 0.5, (c, o))
        include = jnp.concatenate([feat & pol, feat & ~pol], axis=1)
        ta = jnp.where(include, 200, 50).astype(jnp.uint8)
        weights = jax.random.randint(ks[3], (m, c), -127, 128).astype(jnp.int32)
        return {"ta": ta, "weights": weights}

    return make(jax.random.PRNGKey(seed % 2**31))


def make_frames(rng, n, cfg):
    """uint8 [n, Y, X, Z]: each channel its own brightness plus noise."""
    shape = (n, cfg["image_y"], cfg["image_x"], cfg["channels"])
    level = rng.integers(40, 216, (n, 1, 1, cfg["channels"]))
    noise = rng.integers(-80, 81, shape)
    return np.clip(level + noise, 0, 255).astype(np.uint8)


def build_engine(cfg, traffic, model):
    from repro.core.cotm import CoTMConfig, CoTMModel
    from repro.core.patches import PatchSpec
    from repro.serve import ServingEngine

    if traffic.get("mesh"):
        raise ValueError("toy-rgb serves on one device")
    patch = PatchSpec(image_y=cfg["image_y"], image_x=cfg["image_x"],
                      window_y=cfg["window_y"], window_x=cfg["window_x"],
                      stride_y=cfg["stride_y"], stride_x=cfg["stride_x"],
                      channels=cfg["channels"], therm_bits=1)
    if (patch.n_patches, patch.n_literals) != (cfg["n_patches"], cfg["n_literals"]):
        raise ValueError(f"toy-rgb: the program counts {patch.n_patches} patches and "
                         f"{patch.n_literals} literals")
    pcfg = CoTMConfig(n_clauses=cfg["n_clauses"], n_classes=cfg["n_classes"], patch=patch)
    engine = ServingEngine()
    b = cfg["booleanize"]
    engine.register(cfg["arch"], CoTMModel(ta_state=model["ta"], weights=model["weights"]),
                    pcfg, booleanize_method=b["method"],
                    booleanize_kw={"threshold": b["threshold"]})
    return engine, cfg["arch"]


def served_work(engine, arch, cfg, model):
    nonempty = int(np.asarray(engine.servable(arch).nonempty).sum())
    if nonempty != int((model["ta"] >= 128).any(axis=1).sum()):
        raise RuntimeError(f"toy-rgb: served model has {nonempty} nonempty clauses")
    p, lits, m = cfg["n_patches"], cfg["n_literals"], cfg["n_classes"]
    pixels = cfg["image_y"] * cfg["image_x"] * cfg["channels"]
    return {
        "ops_per_frame": 2 * p * nonempty * lits + 2 * nonempty * m + pixels,
        "bytes_per_frame": pixels + 4 * m + 4,
        "model_bytes": -(-cfg["n_clauses"] * lits // 8) + m * cfg["n_clauses"],
        "clause_checks_per_frame": p * nonempty,
    }


def _literals(frames, cfg):
    """uint8 [n, P, 2o]."""
    bits = (frames > cfg["booleanize"]["threshold"]).astype(np.uint8)
    wy, wx, sy, sx = cfg["window_y"], cfg["window_x"], cfg["stride_y"], cfg["stride_x"]
    by = 1 + (cfg["image_y"] - wy) // sy
    bx = 1 + (cfg["image_x"] - wx) // sx
    ny, nx = cfg["image_y"] - wy, cfg["image_x"] - wx
    patches = []
    for y in range(by):
        for x in range(bx):
            win = bits[:, y * sy:y * sy + wy, x * sx:x * sx + wx, :].reshape(len(bits), -1)
            pos = np.array([q < y for q in range(ny)] + [q < x for q in range(nx)], np.uint8)
            patches.append(np.concatenate([win, np.broadcast_to(pos, (len(bits), len(pos)))],
                                          axis=1))
    feats = np.stack(patches, axis=1)
    return np.concatenate([feats, 1 - feats], axis=2)


def reference(frames, cfg, model, weight_bits=8):
    include = model["ta"] >= 128                                    # [C, 2o]
    w = np.clip(model["weights"].astype(np.int64), -127, 127)
    if weight_bits < 8:
        step = 2 ** (8 - weight_bits)
        w = np.clip(np.round(w / step), -(2 ** (weight_bits - 1)),
                    2 ** (weight_bits - 1) - 1).astype(np.int64) * step
    lits = _literals(frames, cfg)
    held = (lits[:, :, None, :] >= include[None, None]).all(axis=3)  # [n, P, C]
    fired = held.any(axis=1) & include.any(axis=1)
    sums = fired.astype(np.int64) @ w.T
    return sums, sums.argmax(axis=1), np.zeros(len(frames), bool)
