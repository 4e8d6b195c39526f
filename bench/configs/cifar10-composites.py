"""The hooks of ``cifar10-composites``: Table III's CIFAR-10 TM-Composite,
four ConvCoTM specialists of 1000 clauses each voting on 32x32x3 frames
(``cifar10-composites.json`` holds the geometry).  The program serves it
as one composite through ``ServingEngine.register`` and one jitted step
per chunk (``jit__classify_composite_step``).

The model is a tuple of four ``{"ta", "weights"}`` banks made from the
seed on the device; frames are three channels of ``system.make_frames``'
coarse field plus noise.  The reference imports nothing of the program.
Its literal order is ``repro.core.patches``': per patch the window's
pixels row-major, each pixel's channels in order, each channel's
thermometer bits in order, then the patch's y- and x-position
thermometers (position p sets its lowest p bits); literals are the
features and then their negations.  A clause fires on a frame iff it is
nonempty and some patch holds every literal it includes; a specialist's
class sums are the fired clauses times its weights; the vote is
``sum_k v_k / max(max_i |v_k,i|, 1)`` and the prediction its first
largest class.
"""

from __future__ import annotations

import numpy as np

import system
from harness import say

STEP_MODULES = ("jit__classify_composite_step",)

#: A pixel this close to its adaptive threshold is decided by rounding.
#: On a TPU v5e (2048 frames) the program's float32 Gaussian mean, at
#: HIGHEST precision, is off the float64 one by 2.1e-5 at most; rounded
#: through bfloat16 (the default precision) by 1.1.
_PIXEL_MARGIN = 1e-3
#: Top two votes this close are decided by rounding.  The program's vote
#: is float32: four quotients of magnitude <= 1, each within about 2 ulp
#: (1.2e-7) of its exact value, and three additions on totals <= 4, each
#: within 2.4e-7, put a class's vote within about 1.2e-6 of the float64
#: one, and the difference of two classes within 2.4e-6: 1e-5 leaves 4x.
#: On a TPU v5e (2048 frames) it was off by 4.0e-7 at most; a bfloat16
#: vote by 1.4e-2.
_VOTE_MARGIN = 1e-5
#: Frames the reference handles at once (its literals and violation
#: counts of one specialist stay under about 350 MB).
_BLOCK = 64


def make_model(jax, cfg, seed):
    """One ``{"ta": uint8 [C, 2o], "weights": int32 [m, C]}`` bank per
    specialist, in one jitted call: each clause includes a count of
    features drawn uniform in ``included_literals``, each in a random
    polarity; a share ``empty_share`` of clauses is empty; weights are
    uniform in ``weight_range``."""
    import jax.numpy as jnp
    # Before any device work: a program that serves no such composite
    # fails here, at once.
    from repro.configs.convcotm import COMPOSITE_CONFIGS

    if cfg["arch"] not in COMPOSITE_CONFIGS:
        raise KeyError(f"the program serves no composite {cfg['arch']!r}")
    lo, hi = cfg["model"]["included_literals"]
    empty_share = cfg["model"]["empty_share"]
    wlo, whi = cfg["model"]["weight_range"]
    m = cfg["n_classes"]
    shapes = [(s["n_clauses"], s["n_literals"] // 2) for s in cfg["specialists"]]

    def bank(key, c, o):
        ks = jax.random.split(key, 5)
        count = jax.random.randint(ks[0], (c, 1), lo, hi + 1)
        count = jnp.where(jax.random.uniform(ks[1], (c, 1)) < empty_share, 0, count)
        feat = jnp.argsort(jax.random.uniform(ks[2], (c, o)), axis=1) < count
        pol = jax.random.bernoulli(ks[3], 0.5, (c, o))
        include = jnp.concatenate([feat & pol, feat & ~pol], axis=1)
        return {"ta": jnp.where(include, 200, 50).astype(jnp.uint8),
                "weights": jax.random.randint(ks[4], (m, c), wlo, whi + 1, jnp.int32)}

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return tuple(bank(k, c, o) for k, (c, o) in zip(keys, shapes))

    return make(jax.random.PRNGKey(system.key_seed(seed)))


def make_frames(rng, n, cfg):
    """uint8 [n, Y, X, Z]: each channel a coarse random field plus noise."""
    y, x, z = cfg["image_y"], cfg["image_x"], cfg["channels"]
    planes = system.make_frames(rng, n * z, y, x)
    return np.ascontiguousarray(planes.reshape(n, z, y, x).transpose(0, 2, 3, 1))


def _check_config(cfg, pcfg, booleanize, ingress=None):
    """The file has to describe what the program serves: every geometry
    key, the weight width, the literal budget and each booleanization."""
    arch = cfg["arch"]
    specs = cfg["specialists"]
    if len(specs) != len(pcfg.specialists):
        raise ValueError(f"{arch}: config file has {len(specs)} specialists, "
                         f"program serves {len(pcfg.specialists)}")
    for k, (s, c) in enumerate(zip(specs, pcfg.specialists)):
        p = c.patch
        served = {
            "image_y": p.image_y, "image_x": p.image_x, "channels": p.channels,
            "n_classes": c.n_classes, "weight_bits": c.weight_bits,
        }
        for key, v in served.items():
            if cfg[key] != v:
                raise ValueError(f"{arch}: config file {key}={cfg[key]}, "
                                 f"program serves {v} in specialist {k}")
        served = {
            "window_y": p.window_y, "window_x": p.window_x,
            "stride_y": p.stride_y, "stride_x": p.stride_x,
            "therm_bits": p.therm_bits, "n_patches": p.n_patches,
            "n_literals": p.n_literals, "n_clauses": c.n_clauses,
            "max_included_literals": c.max_included_literals,
            "booleanize": dict(booleanize[k]),
        }
        for key, v in served.items():
            if s[key] != v:
                raise ValueError(f"{arch}: specialist {k} {key}={s[key]} in the "
                                 f"config file, program serves {v}")
        if ingress is not None:
            b = dict(s["booleanize"])
            if ingress[k].resolved_method != b.pop("method"):
                raise ValueError(f"{arch}: specialist {k} is served with "
                                 f"{ingress[k].method!r}")
            for key, v in b.items():
                if getattr(ingress[k], key) != v:
                    raise ValueError(f"{arch}: specialist {k} {key}={v}, program "
                                     f"{getattr(ingress[k], key)}")


def build_engine(cfg, traffic, model):
    from repro.configs.convcotm import COMPOSITE_BOOLEANIZE, COMPOSITE_CONFIGS
    from repro.core.composites import CompositeModel
    from repro.core.cotm import CoTMModel
    from repro.serve import ServingEngine

    arch = cfg["arch"]
    if traffic.get("mesh"):
        raise ValueError(f"{arch} serves on one device")
    pcfg, booleanize = COMPOSITE_CONFIGS[arch], COMPOSITE_BOOLEANIZE[arch]
    _check_config(cfg, pcfg, booleanize)
    engine = ServingEngine()
    members = tuple(CoTMModel(ta_state=b["ta"], weights=b["weights"]) for b in model)
    engine.register(arch, CompositeModel(members=members), pcfg, booleanize=booleanize)
    _check_config(cfg, pcfg, booleanize, engine.ingress_spec(arch))
    say(f"system: {arch} specialists={len(members)} eval_path={pcfg.specialists[0].eval_path} "
        f"max_batch={engine.max_batch} devices={engine.devices}")
    return engine, arch


def _booleanize_ops(b, values):
    if b["method"] == "thermometer":
        return values * b["levels"]
    if b["method"] == "adaptive":
        return values * (2 * 2 * b["block_size"] + 1)
    raise ValueError(f"no work count for booleanize method {b['method']!r}")


def served_work(engine, arch, cfg, model):
    """The frame's work over each specialist's served nonempty clauses
    (``ServeStats.active_clauses``), which have to be those of the model
    made: ``2*P*C'*2o`` clause checks and ``2*C'*m`` class sums a
    specialist, its booleanization, and the vote (``4*K*m``); bytes are
    the frame in, the sums and prediction out, and the include bits and
    weights at their width once a step."""
    active = tuple(engine.stats(arch).active_clauses)
    made = tuple(int((b["ta"] >= 128).any(axis=1).sum()) for b in model)
    if active != made:
        raise RuntimeError(f"served active clauses {active}, the model made has {made}")
    m, k = cfg["n_classes"], len(cfg["specialists"])
    values = cfg["image_y"] * cfg["image_x"] * cfg["channels"]
    ops = 4 * k * m
    model_bytes = 0
    for s, c in zip(cfg["specialists"], active):
        ops += 2 * s["n_patches"] * c * s["n_literals"] + 2 * c * m
        ops += _booleanize_ops(s["booleanize"], values)
        model_bytes += -(-s["n_clauses"] * s["n_literals"] // 8)
        model_bytes += -(-m * s["n_clauses"] * cfg["weight_bits"] // 8)
    work_ = {"ops_per_frame": ops, "bytes_per_frame": values + 4 * k * m + 4,
             "model_bytes": model_bytes}
    say(f"model: active clauses={list(active)} ops/frame={ops} "
        f"bytes/frame={work_['bytes_per_frame']} model_bytes={model_bytes}")
    return work_


def _bits(frames, b):
    """(bits uint8 [n, Y, X, Z, U], ambiguous bool [n])."""
    x = frames.astype(np.float64)
    if b["method"] == "thermometer":
        th = np.linspace(0.0, 255.0, b["levels"] + 2)[1:-1]
        return (x[..., None] > th).astype(np.uint8), np.zeros(len(x), bool)
    if b["method"] == "adaptive":
        size = b["block_size"]
        sigma = 0.3 * ((size - 1) * 0.5 - 1) + 0.8
        t = np.arange(size) - (size - 1) / 2.0
        g = np.exp(-t ** 2 / (2 * sigma ** 2))
        g /= g.sum()
        mean = x
        for axis in (1, 2):                       # Y, then X; each channel alone
            pad = [(0, 0)] * x.ndim
            pad[axis] = (size // 2, size // 2)
            xp = np.pad(mean, pad, mode="edge")
            n = x.shape[axis]
            mean = sum(gt * np.take(xp, np.arange(i, i + n), axis=axis)
                       for i, gt in enumerate(g))
        margin = x - (mean - b["c"])
        ambiguous = (np.abs(margin) < _PIXEL_MARGIN).reshape(len(x), -1).any(axis=1)
        return (margin > 0).astype(np.uint8)[..., None], ambiguous
    raise ValueError(f"reference has no booleanize method {b['method']!r}")


def _tables(cfg, s):
    """Gather rows and columns [P, Wy*Wx] and position bits [P, pos]."""
    wy, wx, sy, sx = s["window_y"], s["window_x"], s["stride_y"], s["stride_x"]
    ny, nx = cfg["image_y"] - wy, cfg["image_x"] - wx
    iy, ix, pos = [], [], []
    for y in range(1 + ny // sy):
        for x in range(1 + nx // sx):
            iy.append([y * sy + a for a in range(wy) for _ in range(wx)])
            ix.append([x * sx + c for _ in range(wy) for c in range(wx)])
            pos.append([q < y for q in range(ny)] + [q < x for q in range(nx)])
    return np.array(iy), np.array(ix), np.array(pos, np.uint8).reshape(len(iy), ny + nx)


def _weights(w, width, bits):
    """Weights clamped to ``width`` bits, then cut to ``bits`` (the control)."""
    lim = 2 ** (width - 1) - 1
    w = np.clip(w.astype(np.int64), -lim, lim)
    if bits < width:
        step = 2 ** (width - bits)
        w = np.clip(np.round(w / step), -(2 ** (bits - 1)),
                    2 ** (bits - 1) - 1).astype(np.int64) * step
    return w


def reference(frames, cfg, model, weight_bits=8):
    """(per-specialist class sums int64 [n, K, m], predictions [n],
    ambiguous [n]).  The harness asks for the truth with ``weight_bits=8``,
    its default from when every configuration was int8: 8 and above mean
    this configuration's own width (``cfg["weight_bits"]``, 10); below 8
    the weights are cut to that many bits, the control."""
    width = cfg["weight_bits"]
    bits = width if weight_bits >= 8 else weight_bits
    n, m = len(frames), cfg["n_classes"]
    sums = np.zeros((n, len(cfg["specialists"]), m), np.int64)
    ambiguous = np.zeros(n, bool)
    for k, (s, bank) in enumerate(zip(cfg["specialists"], model)):
        include = bank["ta"] >= 128                        # [C, 2o]
        nonempty = include.any(axis=1)
        inc = include.T.astype(np.float32)                 # [2o, C]
        w = _weights(bank["weights"], width, bits)         # [m, C]
        iy, ix, pos = _tables(cfg, s)
        for a in range(0, n, _BLOCK):
            block, amb = _bits(frames[a:a + _BLOCK], s["booleanize"])
            ambiguous[a:a + _BLOCK] |= amb
            b = len(block)
            win = block[:, iy, ix].reshape(b, len(iy), -1)  # [b, P, Wy*Wx*Z*U]
            feats = np.concatenate([win, np.broadcast_to(pos, (b,) + pos.shape)], axis=2)
            absent = np.concatenate([1 - feats, feats], axis=2)   # 1 - literals
            viol = absent.reshape(-1, absent.shape[2]).astype(np.float32) @ inc
            fired = (viol.reshape(b, len(iy), -1) == 0).any(axis=1) & nonempty
            sums[a:a + b, k] = fired.astype(np.int64) @ w.T
    v = sums.astype(np.float64)
    votes = (v / np.maximum(np.abs(v).max(axis=2, keepdims=True), 1.0)).sum(axis=1)
    top2 = np.sort(votes, axis=1)[:, -2:]
    ambiguous |= top2[:, 1] - top2[:, 0] < _VOTE_MARGIN
    return sums, votes.argmax(axis=1), ambiguous
