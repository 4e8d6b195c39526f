"""Registry of ConvCoTM evaluation paths.

Every path computes Eq. (3) class sums ``int32 [B, m]`` from one image
batch's literals and a :class:`~repro.serve.servable.ServableModel`'s
frozen fields.  Paths declare their preferred *literal input form* so
callers (``core.cotm.infer``, the serving engine) convert literals
exactly once:

  * ``dense``  — uint8 0/1 literals ``[B, P, 2o]``;
  * ``packed`` — uint32 words ``[B, P, W]`` (LSB-first, see
    ``core.patches.pack_bits``).

Beyond literals, every path also owns its full **raw -> class sums**
graph: :data:`RAW` names the third request form (raw pixel batches,
uint8 ``[B, H, W]``), and each :class:`EvalPath` carries an
``ingress_fn`` — ``(IngressSpec, raw) -> literals`` in the path's input
form, pure jnp — so :func:`run_path_raw` traces booleanize -> patches ->
literals -> pack -> clause eval -> class sums into ONE jitted graph with
a single H2D copy (the serving engine's ``classify_raw_step``).  The
default ``ingress_fn`` is :func:`repro.core.ingress.apply_ingress`;
kernel-backed paths may substitute one that drops into the Pallas
ingress kernel.  A path may instead carry a ``raw_fn`` — raw -> class
sums with no literal tensor at all — which :func:`run_path_raw` calls
in place of ``ingress_fn`` then ``fn``.  ``matmul`` does: its raw form
folds each literal into its negation and checks clauses with one
convolution over the booleanized frame
(:func:`repro.core.clauses.eval_clauses_folded`), so no patch is
gathered; its literal form keeps the dot over ``[B, P, 2o]``.

Sparse paths and fallbacks (ARCHITECTURE.md §Sparsity)
------------------------------------------------------
Paths marked ``needs_sparsity`` consume the active-clause image derived
at freeze time (``servable.sparsity``, see
:func:`repro.serve.servable.analyze_sparsity`): empty clauses are pruned
from the clause pool entirely, so work scales with the number of clauses
that *can* fire.  When a servable carries no sparsity analysis (e.g.
frozen inline under jit, or clause-sharded across a mesh where the
active set is not shard-uniform), :func:`resolve_path` substitutes the
path's declared ``fallback`` — a registered dense twin with the same
input form and bit-identical outputs — so every caller keeps working.

Tunable parameters
------------------
``tunable`` lists candidate static parameter sets (tuples of ``(name,
value)`` pairs, hashable so they can key jit) the autotuner
(``serve/autotune.py``) may sweep per (bucket, geometry) — grid/block
shapes and the CSRF toggle for the Pallas-backed kernels.  ``()`` (the
path's defaults) is always a candidate; non-default sets are only worth
sweeping where the Pallas kernels actually compile (TPU), and the
autotuner restricts itself accordingly.

Replaces the stringly-typed ``eval_path`` if/elif chain that used to live
in ``core/cotm.py``: new paths register here and are immediately usable
by ``CoTMConfig(eval_path=...)``, the engine, benchmarks and tests.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax

from repro.core import clauses as cl
from repro.core.ingress import IngressSpec, apply_ingress, feature_bits

__all__ = [
    "EvalPath",
    "Params",
    "register_path",
    "get_path",
    "available_paths",
    "resolve_path",
    "degraded_fallback",
    "run_path",
    "run_path_raw",
    "folded_convolution",
    "DENSE",
    "PACKED",
    "RAW",
]

#: fn(literals, include, include_packed, nonempty, weights, [sparsity,]
#:    **params) -> int32 [B, m]; the ``sparsity`` positional is passed to
#: ``needs_sparsity`` paths only.
PathFn = Callable[..., jax.Array]

#: ingress_fn(spec, raw) -> literals in the path's input form (pure jnp)
IngressFn = Callable[[IngressSpec, jax.Array], jax.Array]

#: raw_fn(spec, raw, include, include_packed, nonempty, weights,
#:        **params) -> int32 [B, m], the raw form with no literal tensor.
RawFn = Callable[..., jax.Array]

#: A static parameter set: hashable ((name, value), ...) pairs.
Params = Tuple[Tuple[str, object], ...]

DENSE = "dense"
PACKED = "packed"
#: The raw request form: uint8 pixel batches, converted on device by the
#: path's ``ingress_fn`` inside the same jitted graph as evaluation.
RAW = "raw"

#: Block-shape / CSRF candidates for the Pallas-backed kernels (swept by
#: the autotuner on backends where the kernels compile).
_KERNEL_TUNABLE: Tuple[Params, ...] = (
    (),
    (("block_b", 16),),
    (("block_p", 256),),
    (("block_b", 16), ("block_p", 256)),
    (("csrf", False),),
)


@dataclasses.dataclass(frozen=True)
class EvalPath:
    """A registered evaluation path (name, literal form, eval + ingress fns).

    ``needs_sparsity`` paths receive ``servable.sparsity`` as an extra
    positional argument; ``fallback`` names the bit-identical dense twin
    used when no sparsity analysis is attached (must share
    ``input_form``).  ``tunable`` lists static parameter sets the
    autotuner may sweep (the empty set — path defaults — always works).
    ``raw_fn``, when set, is the path's whole raw form (raw -> class
    sums), used by :func:`run_path_raw` in place of ``ingress_fn`` and
    ``fn``.
    """

    name: str
    input_form: str          # DENSE | PACKED
    fn: PathFn
    ingress_fn: IngressFn = apply_ingress
    raw_fn: Optional[RawFn] = None
    needs_sparsity: bool = False
    fallback: Optional[str] = None
    tunable: Tuple[Params, ...] = ((),)

    def __post_init__(self):
        if self.input_form not in (DENSE, PACKED):
            raise ValueError(f"input_form must be '{DENSE}' or '{PACKED}'")
        if self.needs_sparsity and self.fallback is None:
            raise ValueError(
                f"sparse path {self.name!r} must declare a dense fallback"
            )

    def ingress_spec(self, patch, method: str = "threshold", **kw) -> IngressSpec:
        """The :class:`IngressSpec` matching this path's literal form."""
        return IngressSpec(
            patch=patch, method=method, packed=self.input_form == PACKED, **kw
        )


_REGISTRY: dict[str, EvalPath] = {}


def register_path(
    name: str,
    input_form: str,
    *,
    ingress_fn: Optional[IngressFn] = None,
    raw_fn: Optional[RawFn] = None,
    needs_sparsity: bool = False,
    fallback: Optional[str] = None,
    tunable: Tuple[Params, ...] = ((),),
) -> Callable[[PathFn], PathFn]:
    """Decorator: register ``fn`` as evaluation path ``name``.

    ``ingress_fn`` overrides the default device ingress for this path
    (same contract: ``(IngressSpec, raw) -> literals`` in ``input_form``,
    jit-composable); ``raw_fn`` replaces the raw form whole.
    ``fallback`` (required with ``needs_sparsity``) must already be
    registered with the same input form.
    """

    def deco(fn: PathFn) -> PathFn:
        if name in _REGISTRY:
            raise ValueError(f"eval path {name!r} already registered")
        if fallback is not None:
            fb = get_path(fallback)    # fail fast on unknown fallbacks
            if fb.input_form != input_form:
                raise ValueError(
                    f"fallback {fallback!r} input form {fb.input_form!r} != "
                    f"{input_form!r}"
                )
        _REGISTRY[name] = EvalPath(
            name=name,
            input_form=input_form,
            fn=fn,
            ingress_fn=ingress_fn or apply_ingress,
            raw_fn=raw_fn,
            needs_sparsity=needs_sparsity,
            fallback=fallback,
            tunable=tunable,
        )
        return fn

    return deco


def get_path(name: str) -> EvalPath:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown eval path {name!r}; registered: {available_paths()}"
        ) from None


def available_paths() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_path(path: EvalPath, servable) -> EvalPath:
    """The path actually evaluated for ``servable``: sparse paths without
    an attached sparsity analysis resolve to their dense fallback
    (bit-identical by the multi-path equivalence contract)."""
    if path.needs_sparsity and getattr(servable, "sparsity", None) is None:
        return get_path(path.fallback)
    return path


#: The degradation chain: where a path falls back to when its dispatches
#: keep failing (the circuit breaker in serve/faults.py).  One step per
#: trip — sparse paths first shed their sparsity machinery onto the
#: declared dense twin, kernel-backed paths shed the Pallas kernels onto
#: plain XLA math, and everything bottoms out at "dense", the simplest
#: reference-equal path.  Unlike :func:`resolve_path`'s jit-internal
#: substitution (same input form, inside one graph), this chain is
#: walked at the engine registry level, where the ingress spec is
#: rebuilt — so a step may change literal input form (fused -> matmul).
_DEGRADED_CHAIN = {
    "fused_sparse": "fused",
    "sparse": "bitpacked",
    "matmul_sparse": "matmul",
    "fused": "matmul",
    "kernel": "matmul",
    "bitpacked": "dense",
    "matmul": "dense",
    "dense": None,
}


def degraded_fallback(name: str) -> Optional[str]:
    """The next path down the degradation chain for ``name`` (None when
    already at the bottom).  Paths outside the built-in chain fall back
    to their declared ``fallback``, else straight to ``dense``."""
    if name in _DEGRADED_CHAIN:
        return _DEGRADED_CHAIN[name]
    path = get_path(name)
    return path.fallback or "dense"


def _resolved(path: EvalPath, servable, params: Params):
    """(the path evaluated, its params, its servable arguments)."""
    resolved = resolve_path(path, servable)
    if resolved is not path:
        # Fallback substitution: tuned params belong to the sparse path,
        # not its dense twin — run the twin at its defaults.
        path, params = resolved, ()
    args = (
        servable.include,
        servable.include_packed,
        servable.nonempty,
        servable.weights,
    )
    if path.needs_sparsity:
        args = args + (servable.sparsity,)
    return path, params, args


def run_path(
    path: EvalPath, servable, literals: jax.Array, params: Params = ()
) -> jax.Array:
    """Class sums int32 [B, m]; ``literals`` must be in ``path.input_form``.

    ``params`` is a static parameter set from ``path.tunable`` (autotuner
    winners); ``()`` runs the path defaults.
    """
    path, params, args = _resolved(path, servable, params)
    return path.fn(literals, *args, **dict(params))


def run_path_raw(
    path: EvalPath,
    servable,
    raw: jax.Array,
    ingress: IngressSpec,
    params: Params = (),
) -> jax.Array:
    """Class sums int32 [B, m] straight from raw pixels (the :data:`RAW`
    form): the path's ``raw_fn`` when it has one, else its own
    ingress_fn then its eval fn — one traceable graph with no host
    materialization in between."""
    resolved, resolved_params, args = _resolved(path, servable, params)
    if resolved.raw_fn is not None:
        return resolved.raw_fn(ingress, raw, *args, **dict(resolved_params))
    if ingress.packed != (path.input_form == PACKED):
        ingress = dataclasses.replace(ingress, packed=path.input_form == PACKED)
    return run_path(path, servable, path.ingress_fn(ingress, raw), params)


def folded_convolution(path_name: str, servable) -> bool:
    """Whether the raw form of ``path_name`` checks ``servable``'s clauses
    as the folded convolution of :func:`repro.core.clauses.
    eval_clauses_folded` (the count behind ``ServeStats.folded_checks``)."""
    path = resolve_path(get_path(path_name), servable)
    return path.raw_fn is _matmul_raw


# --- the built-in paths ----------------------------------------------------

@register_path("dense", DENSE)
def _dense(lits, include, include_packed, nonempty, weights):
    fired = cl.eval_clauses_dense(lits, include)
    return cl.class_sums(fired, weights)


def _matmul_raw(ingress, raw, include, include_packed, nonempty, weights):
    fired = cl.eval_clauses_folded(
        feature_bits(ingress, raw), ingress.patch, include, nonempty
    )
    return cl.class_sums(fired, weights)


@register_path("matmul", DENSE, raw_fn=_matmul_raw)
def _matmul(lits, include, include_packed, nonempty, weights):
    fired = cl.eval_clauses_matmul(lits, include, nonempty)
    return cl.class_sums(fired, weights)


@register_path("bitpacked", PACKED)
def _bitpacked(lits, include, include_packed, nonempty, weights):
    fired = cl.eval_clauses_bitpacked(lits, include_packed, nonempty)
    return cl.class_sums(fired, weights)


@register_path("kernel", PACKED, tunable=_KERNEL_TUNABLE)
def _kernel(lits, include, include_packed, nonempty, weights, **params):
    from repro.kernels import ops as kops

    fired = kops.clause_eval(lits, include_packed, nonempty, **params)
    return cl.class_sums(fired, weights)


@register_path("fused", PACKED, tunable=_KERNEL_TUNABLE)
def _fused(lits, include, include_packed, nonempty, weights, **params):
    from repro.kernels import ops as kops

    return kops.fused_infer(lits, include_packed, nonempty, weights, **params)


# --- clause-sparsity fast paths (active-clause pool; see module doc) -------

@register_path(
    "sparse", PACKED, needs_sparsity=True, fallback="bitpacked",
    tunable=_KERNEL_TUNABLE,
)
def _sparse(lits, include, include_packed, nonempty, weights, sparsity, **params):
    from repro.kernels import ops as kops

    fired = kops.clause_eval_sparse(lits, sparsity.exclude_packed, **params)
    return cl.class_sums(fired, sparsity.weights)


@register_path(
    "fused_sparse", PACKED, needs_sparsity=True, fallback="fused",
    tunable=_KERNEL_TUNABLE,
)
def _fused_sparse(lits, include, include_packed, nonempty, weights, sparsity, **params):
    from repro.kernels import ops as kops

    return kops.fused_infer_sparse(
        lits, sparsity.exclude_packed, sparsity.weights, **params
    )


@register_path("matmul_sparse", DENSE, needs_sparsity=True, fallback="matmul")
def _matmul_sparse(lits, include, include_packed, nonempty, weights, sparsity):
    from repro.kernels import ops as kops

    return kops.matmul_sparse_infer(lits, sparsity.include, sparsity.weights)
