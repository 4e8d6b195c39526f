"""TM404: integer-range interval analysis over clause-eval / class-sum
jaxprs.

The analysis walks a jaxpr with one abstract value per array: a single
``[lo, hi]`` interval over the *mathematical* integers bounding every
element.  All values in the TM eval pipeline are integer-valued — even
the bf16/fp32 matmul formulations only ever hold exact small integers —
so one engine proves both contracts:

  * **integer overflow**: an eqn whose mathematical result interval
    escapes its integer output dtype's representable range (e.g. an int8
    accumulator fed more than 127 ones) is a finding; the interval is
    clamped to the dtype range and the walk continues, so one overflow
    does not cascade into noise.
  * **float exactness**: a float-typed value whose magnitude bound
    exceeds the dtype's exact-integer range (bf16: 2^8, fp16: 2^11,
    fp32: 2^24, fp64: 2^53) may round — fatal for the ``viol == 0.0``
    clause-firing compare — and is a finding at the producing eqn or the
    float->int convert.

Primitives without a handler degrade soundly: integer outputs get the
full dtype range (no finding — unknown, not proven wrong), float outputs
get the dtype's exact range.  Axes that are only ever OR-reduced
(batch, patches) don't influence intervals, so the driver traces with
the *contracted* axes (clauses, literal words, classes) at
``repro.core.cotm.MAX_GEOMETRY`` and tiny parallel axes — the proof is
still the envelope proof.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from tools.tmverify.core import Baseline, Finding, VerifyResult

__all__ = [
    "Interval",
    "analyze_fn",
    "check_intervals",
    "dtype_interval",
    "exact_int_bound",
]


@dataclasses.dataclass(frozen=True)
class Interval:
    lo: int
    hi: int

    def __post_init__(self):
        assert self.lo <= self.hi, (self.lo, self.hi)

    def union(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    def magnitude(self) -> int:
        return max(abs(self.lo), abs(self.hi))


BOOL01 = Interval(0, 1)

#: Largest N with every integer in [-N, N] exactly representable.
_EXACT_FLOAT_BOUND = {
    "bfloat16": 1 << 8,
    "float16": 1 << 11,
    "float32": 1 << 24,
    "float64": 1 << 53,
}


def exact_int_bound(dtype) -> int:
    return _EXACT_FLOAT_BOUND[np.dtype(dtype).name if np.dtype(dtype).name
                              in _EXACT_FLOAT_BOUND else _bf16_name(dtype)]


def _bf16_name(dtype) -> str:
    # jax's bfloat16 is not a numpy builtin; match by name attribute.
    name = getattr(dtype, "name", str(dtype))
    if name not in _EXACT_FLOAT_BOUND:
        raise KeyError(name)
    return name


def _is_float(dtype) -> bool:
    name = getattr(dtype, "name", str(np.dtype(dtype)))
    return name in _EXACT_FLOAT_BOUND or np.issubdtype(
        np.dtype(dtype) if name != "bfloat16" else np.float32, np.floating
    )


def dtype_interval(dtype) -> Interval:
    """The representable (integer dtypes) or exactly-representable
    (float dtypes) integer interval of ``dtype``."""
    name = getattr(dtype, "name", str(np.dtype(dtype)))
    if name == "bool":
        return BOOL01
    if name in _EXACT_FLOAT_BOUND:
        b = _EXACT_FLOAT_BOUND[name]
        return Interval(-b, b)
    np_dtype = np.dtype(dtype)
    if np.issubdtype(np_dtype, np.floating):
        b = _EXACT_FLOAT_BOUND[np_dtype.name]
        return Interval(-b, b)
    info = np.iinfo(np_dtype)
    return Interval(int(info.min), int(info.max))


def _fits(iv: Interval, dtype) -> bool:
    dr = dtype_interval(dtype)
    return dr.lo <= iv.lo and iv.hi <= dr.hi


def _clamp(iv: Interval, dtype) -> Interval:
    dr = dtype_interval(dtype)
    return Interval(max(iv.lo, dr.lo), min(iv.hi, dr.hi))


def _next_mask(hi: int) -> int:
    """Smallest 2^k - 1 >= hi (bitwise-op upper bound)."""
    m = 0
    while m < hi:
        m = (m << 1) | 1
    return m


# ---------------------------------------------------------------------------
# Primitive transfer functions


def _products(a: Interval, b: Interval) -> Tuple[int, int]:
    ps = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
    return min(ps), max(ps)


def _dot_general(eqn, ins: List[Interval]) -> Interval:
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    lhs_shape = eqn.invars[0].aval.shape
    k = 1
    for d in lhs_contract:
        k *= int(lhs_shape[d])
    pmin, pmax = _products(ins[0], ins[1])
    return Interval(k * pmin, k * pmax)


def _conv(eqn, ins: List[Interval]) -> Interval:
    """Each output element sums at most kernel window x input features
    products (the rhs's size over its output-feature dim); padding and
    dilation only drop terms, so the bound widens to include 0."""
    rhs_shape = eqn.invars[1].aval.shape
    out_feature_dim = eqn.params["dimension_numbers"].rhs_spec[0]
    k = 1
    for n in rhs_shape:
        k *= int(n)
    k //= max(1, int(rhs_shape[out_feature_dim]))
    pmin, pmax = _products(ins[0], ins[1])
    return Interval(k * min(pmin, 0), k * max(pmax, 0))


def _reduce_sum(eqn, ins: List[Interval]) -> Interval:
    shape = eqn.invars[0].aval.shape
    n = 1
    for d in eqn.params["axes"]:
        n *= int(shape[d])
    return Interval(n * ins[0].lo, n * ins[0].hi)


def _bitwise(eqn, ins: List[Interval]) -> Interval:
    if all(iv.lo >= 0 for iv in ins):
        if all(iv.hi <= 1 for iv in ins):
            return BOOL01
        if eqn.primitive.name == "and":
            return Interval(0, min(_next_mask(iv.hi) for iv in ins))
        return Interval(0, max(_next_mask(iv.hi) for iv in ins))
    return dtype_interval(eqn.outvars[0].aval.dtype)


def _shift_left(eqn, ins: List[Interval]) -> Interval:
    s = ins[1]
    if s.lo == s.hi and ins[0].lo >= 0:
        return Interval(ins[0].lo << s.lo, ins[0].hi << s.lo)
    return dtype_interval(eqn.outvars[0].aval.dtype)


def _iota(eqn, ins) -> Interval:
    shape = eqn.params.get("shape") or eqn.outvars[0].aval.shape
    dim = eqn.params.get("dimension", 0)
    return Interval(0, max(0, int(shape[dim]) - 1))


def _argminmax(eqn, ins) -> Interval:
    shape = eqn.invars[0].aval.shape
    axes = eqn.params.get("axes", (0,))
    return Interval(0, max(0, int(shape[axes[0]]) - 1))


_HANDLERS: Dict[str, Callable] = {
    "add": lambda e, i: Interval(i[0].lo + i[1].lo, i[0].hi + i[1].hi),
    "sub": lambda e, i: Interval(i[0].lo - i[1].hi, i[0].hi - i[1].lo),
    "mul": lambda e, i: Interval(*_products(i[0], i[1])),
    "neg": lambda e, i: Interval(-i[0].hi, -i[0].lo),
    "max": lambda e, i: Interval(max(i[0].lo, i[1].lo), max(i[0].hi, i[1].hi)),
    "min": lambda e, i: Interval(min(i[0].lo, i[1].lo), min(i[0].hi, i[1].hi)),
    "dot_general": _dot_general,
    "conv_general_dilated": _conv,
    "reduce_sum": _reduce_sum,
    "reduce_max": lambda e, i: i[0],
    "reduce_min": lambda e, i: i[0],
    "reduce_and": lambda e, i: BOOL01,
    "reduce_or": lambda e, i: BOOL01,
    "and": _bitwise,
    "or": _bitwise,
    "xor": _bitwise,
    "not": lambda e, i: (
        BOOL01 if e.outvars[0].aval.dtype == np.dtype(bool)
        else dtype_interval(e.outvars[0].aval.dtype)
    ),
    "population_count": lambda e, i: Interval(
        0, np.dtype(e.invars[0].aval.dtype).itemsize * 8
    ),
    "clz": lambda e, i: Interval(
        0, np.dtype(e.invars[0].aval.dtype).itemsize * 8
    ),
    "shift_left": _shift_left,
    "shift_right_logical": lambda e, i: Interval(0, max(0, i[0].hi)),
    "eq": lambda e, i: BOOL01,
    "ne": lambda e, i: BOOL01,
    "lt": lambda e, i: BOOL01,
    "le": lambda e, i: BOOL01,
    "gt": lambda e, i: BOOL01,
    "ge": lambda e, i: BOOL01,
    "select_n": lambda e, i: _union_all(i[1:]),
    "concatenate": lambda e, i: _union_all(i),
    "pad": lambda e, i: i[0].union(i[1]),
    "broadcast_in_dim": lambda e, i: i[0],
    "reshape": lambda e, i: i[0],
    "transpose": lambda e, i: i[0],
    "squeeze": lambda e, i: i[0],
    "expand_dims": lambda e, i: i[0],
    "copy": lambda e, i: i[0],
    "rev": lambda e, i: i[0],
    "slice": lambda e, i: i[0],
    "dynamic_slice": lambda e, i: i[0],
    "gather": lambda e, i: i[0],
    "device_put": lambda e, i: i[0],
    "stop_gradient": lambda e, i: i[0],
    "iota": _iota,
    "argmax": _argminmax,
    "argmin": _argminmax,
    "integer_pow": lambda e, i: _int_pow(e, i),
    "clamp": lambda e, i: Interval(
        max(i[1].lo, i[0].lo), min(i[1].hi, i[2].hi)
    ) if i[0].lo <= i[2].hi else i[1],
}


def _union_all(ivs: Sequence[Interval]) -> Interval:
    out = ivs[0]
    for iv in ivs[1:]:
        out = out.union(iv)
    return out


def _int_pow(eqn, ins: List[Interval]) -> Interval:
    p = int(eqn.params["y"])
    cands = [ins[0].lo ** p, ins[0].hi ** p]
    if ins[0].lo < 0 < ins[0].hi:
        cands.append(0)
    return Interval(min(cands), max(cands))


# ---------------------------------------------------------------------------
# Jaxpr walk


@dataclasses.dataclass
class IntervalStats:
    eqns: int = 0
    handled: int = 0
    #: widest integer-typed eqn output interval (the proven accumulator
    #: bound reported in REPORT.md)
    widest_int: Optional[Interval] = None

    def note_int(self, iv: Interval) -> None:
        if self.widest_int is None or iv.magnitude() > self.widest_int.magnitude():
            self.widest_int = iv


def _const_interval(val) -> Interval:
    arr = np.asarray(val)
    if arr.dtype == np.dtype(bool):
        arr = arr.astype(np.int64)
    if arr.size == 0:
        return Interval(0, 0)
    return Interval(int(arr.min()), int(arr.max()))


def _walk(jaxpr, env: Dict, target: str, findings: List[Finding],
          stats: IntervalStats, prefix: str = "") -> None:
    def read(atom) -> Interval:
        if hasattr(atom, "val"):          # Literal
            return _const_interval(atom.val)
        return env[atom]

    for idx, eqn in enumerate(jaxpr.eqns):
        stats.eqns += 1
        name = eqn.primitive.name
        key = f"{prefix}{idx}:{name}"
        ins = [read(v) for v in eqn.invars]

        if name == "pjit":
            inner = eqn.params["jaxpr"]
            inner_jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner
            sub_env: Dict = {}
            for cv, cval in zip(inner_jaxpr.constvars,
                                getattr(inner, "consts", [])):
                sub_env[cv] = _const_interval(cval)
            for var, iv in zip(inner_jaxpr.invars, ins):
                sub_env[var] = iv
            _walk(inner_jaxpr, sub_env, target, findings, stats,
                  prefix=f"{key}/")
            for out, inner_out in zip(eqn.outvars, inner_jaxpr.outvars):
                env[out] = (sub_env[inner_out]
                            if not hasattr(inner_out, "val")
                            else _const_interval(inner_out.val))
            continue

        handler = _HANDLERS.get(name)
        if name == "convert_element_type":
            out_dtype = eqn.outvars[0].aval.dtype
            iv = ins[0]
            if _is_float(eqn.invars[0].aval.dtype) and not _is_float(out_dtype):
                # float -> int: the float side must have stayed exact.
                src_bound = exact_int_bound(eqn.invars[0].aval.dtype)
                if iv.magnitude() > src_bound:
                    findings.append(Finding(
                        "TM404", target, f"{key}:inexact-src",
                        f"float->int convert of values in [{iv.lo}, "
                        f"{iv.hi}] whose magnitude exceeds the source "
                        f"dtype's exact-integer bound {src_bound}",
                    ))
                    iv = _clamp(iv, out_dtype)
            if not _is_float(out_dtype) and not _fits(iv, out_dtype):
                findings.append(Finding(
                    "TM404", target, f"{key}:narrowing",
                    f"convert to {out_dtype} of values in [{iv.lo}, "
                    f"{iv.hi}] overflows its range "
                    f"[{dtype_interval(out_dtype).lo}, "
                    f"{dtype_interval(out_dtype).hi}]",
                ))
                iv = _clamp(iv, out_dtype)
            if _is_float(out_dtype) and iv.magnitude() > exact_int_bound(out_dtype):
                findings.append(Finding(
                    "TM404", target, f"{key}:inexact",
                    f"convert to {out_dtype} of integers in [{iv.lo}, "
                    f"{iv.hi}] exceeds the exact-integer bound "
                    f"{exact_int_bound(out_dtype)} — equality compares "
                    f"downstream may misfire",
                ))
            env[eqn.outvars[0]] = iv
            stats.handled += 1
            if not _is_float(out_dtype):
                stats.note_int(iv)
            continue

        if handler is not None:
            iv = handler(eqn, ins)
            stats.handled += 1
        else:
            iv = dtype_interval(eqn.outvars[0].aval.dtype)

        out_dtype = eqn.outvars[0].aval.dtype
        if handler is not None and not _is_float(out_dtype) \
                and str(out_dtype) != "bool" and not _fits(iv, out_dtype):
            findings.append(Finding(
                "TM404", target, f"{key}:overflow",
                f"{name} result interval [{iv.lo}, {iv.hi}] overflows "
                f"{out_dtype} "
                f"[{dtype_interval(out_dtype).lo}, "
                f"{dtype_interval(out_dtype).hi}]",
            ))
            iv = _clamp(iv, out_dtype)
        if handler is not None and _is_float(out_dtype) \
                and iv.magnitude() > exact_int_bound(out_dtype):
            findings.append(Finding(
                "TM404", target, f"{key}:inexact",
                f"{name} result interval [{iv.lo}, {iv.hi}] exceeds "
                f"{out_dtype}'s exact-integer bound "
                f"{exact_int_bound(out_dtype)}",
            ))
        if not _is_float(out_dtype):
            stats.note_int(iv)
        for out in eqn.outvars:
            env[out] = iv


def analyze_fn(
    fn, arg_specs: Sequence, seeds: Sequence[Interval], target: str
) -> Tuple[List[Finding], IntervalStats]:
    """Trace ``fn`` at ``arg_specs`` (ShapeDtypeStructs) and walk the
    jaxpr with per-argument seed intervals."""
    import jax

    closed = jax.make_jaxpr(fn)(*arg_specs)
    jaxpr = closed.jaxpr
    if len(seeds) != len(jaxpr.invars):
        raise ValueError(
            f"{target}: {len(seeds)} seeds for {len(jaxpr.invars)} invars"
        )
    env: Dict = {}
    for cv, cval in zip(jaxpr.constvars, closed.consts):
        env[cv] = _const_interval(cval)
    for var, iv in zip(jaxpr.invars, seeds):
        env[var] = iv
    findings: List[Finding] = []
    stats = IntervalStats()
    _walk(jaxpr, env, target, findings, stats)
    return findings, stats


# ---------------------------------------------------------------------------
# Driver: the envelope proofs at MAX_GEOMETRY


def _max_geometry_cases():
    """(target, fn, arg ShapeDtypeStructs, seed intervals) at the
    MAX_GEOMETRY envelope.

    Contracted axes (clause pool C, literal words W, dense literals 2o,
    classes m) sit at the envelope; batch and patch axes are tiny because
    they are only ever OR-reduced or parallel — their extent never feeds
    an accumulator.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import clauses as cl
    from repro.core.cotm import MAX_GEOMETRY, WEIGHT_MAX, WEIGHT_MIN
    from repro.core.patches import PatchSpec
    from repro.kernels import ref

    G = MAX_GEOMETRY
    C, m, L = G.n_clauses, G.n_classes, G.n_literals
    W = L // 32
    B, P = 4, 8  # parallel axes; see docstring
    u8, u32, i8 = jnp.uint8, jnp.uint32, jnp.int8
    S = jax.ShapeDtypeStruct
    bit = Interval(0, 1)
    word = Interval(0, (1 << 32) - 1)
    wt = Interval(WEIGHT_MIN, WEIGHT_MAX)

    def popcount_chain(lit_packed, exclude_packed):
        # jnp mirror of the sparse kernels' per-word accumulation
        # (clause_eval.py / fused_infer.py): sum of W popcounts into
        # int32.
        miss = ~(lit_packed[:, :, None, :] | exclude_packed[None, None])
        return jnp.sum(
            jax.lax.population_count(miss).astype(jnp.int32), axis=-1
        )

    def class_sum_tile_f32(fired, w):
        # fp32 accumulation tile of the Pallas class-sum/fused kernels
        # at the largest block_c (128): exactness needs 127 * 128 < 2^24.
        part = jax.lax.dot_general(
            fired.astype(jnp.float32), w.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return part.astype(jnp.int32)

    def train_eval(literals, include, weights):
        return cl.class_sums(
            cl.eval_clauses_matmul(literals, include), weights
        )

    # The folded raw check (serve/paths.py, matmul's raw form) at the
    # widest window the envelope admits with position bits left over:
    # 36x36x3 window bits + 24 position bits, 2o = 7824 <= L.
    fold_spec = PatchSpec(image_x=48, image_y=48, window_x=36, window_y=36,
                          channels=3)
    assert fold_spec.n_literals <= L

    def folded_eval(bits, include, weights):
        return cl.class_sums(
            cl.eval_clauses_folded(bits, fold_spec, include), weights
        )

    return [
        ("ir:ref.class_sum", ref.class_sum_ref,
         [S((B, C), u8), S((m, C), i8)], [bit, wt]),
        ("ir:ref.clause_eval", ref.clause_eval_ref,
         [S((B, P, W), u32), S((C, W), u32), S((C,), u8)],
         [word, word, bit]),
        ("ir:ref.fused_infer", ref.fused_infer_ref,
         [S((B, P, W), u32), S((C, W), u32), S((C,), u8), S((m, C), i8)],
         [word, word, bit, wt]),
        ("ir:ref.matmul_sparse_infer", ref.matmul_sparse_infer_ref,
         [S((B, P, L), u8), S((C, L), u8), S((m, C), i8)],
         [bit, bit, wt]),
        ("ir:kernel.popcount_chain", popcount_chain,
         [S((B, P, W), u32), S((C, W), u32)], [word, word]),
        ("ir:kernel.class_sum_tile_f32", class_sum_tile_f32,
         [S((B, 128), u8), S((m, 128), i8)], [bit, wt]),
        ("ir:train.eval_matmul", train_eval,
         [S((B, P, L), u8), S((C, L), u8), S((m, C), i8)],
         [bit, bit, wt]),
        ("ir:serve.eval_folded", folded_eval,
         [S((B, 48, 48, 3, 1), u8), S((C, fold_spec.n_literals), u8),
          S((m, C), i8)],
         [bit, bit, wt]),
    ]


def check_intervals(result: VerifyResult, baseline: Baseline) -> None:
    from repro.core.cotm import MAX_GEOMETRY

    lines = result.summary.setdefault("TM404", [])
    G = MAX_GEOMETRY
    lines.append(
        f"envelope: n_clauses={G.n_clauses} n_classes={G.n_classes} "
        f"n_literals={G.n_literals} n_patches={G.n_patches} "
        f"batch={G.batch}"
    )
    for target, fn, specs, seeds in _max_geometry_cases():
        result.checks += 1
        result.targets.append(target)
        findings, stats = analyze_fn(fn, specs, seeds, target)
        for f in findings:
            result.add(baseline, f)
        widest = stats.widest_int
        lines.append(
            f"{target}: {stats.eqns} eqns ({stats.handled} handled), "
            + (f"widest integer interval [{widest.lo}, {widest.hi}]"
               if widest else "no integer eqn outputs")
            + (f", {len(findings)} finding(s)" if findings else ", clean")
        )
