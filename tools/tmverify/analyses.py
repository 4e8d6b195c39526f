"""TM401-TM403: donation, host-transfer and recompile-key audits.

Each rule has a pure core (operating on lowered text / a jaxpr / a
path-like object) so tests can drive negative fixtures directly, plus a
``check_*`` driver that walks the enumerated targets or the live path
registry and files findings into a :class:`~tools.tmverify.core.VerifyResult`.
"""

from __future__ import annotations

from typing import Iterable, Iterator, List, Sequence, Tuple

from tools.tmverify.core import Baseline, Finding, VerifyResult
from tools.tmverify.targets import StepTarget, VerifyConfig, buckets_for

__all__ = [
    "aliased_output_count",
    "audit_registry_path",
    "check_donation",
    "check_host_transfers",
    "check_recompile_keys",
    "forbidden_primitives",
    "iter_eqns",
]

# ---------------------------------------------------------------------------
# TM401 — donation audit


def aliased_output_count(lowered_text: str) -> int:
    """How many input->output aliases the lowered module actually carries.

    XLA marks each honoured donation with a ``tf.aliasing_output`` arg
    attribute in the StableHLO module; a donation jit accepted but could
    not alias (dtype/shape mismatch, consumed-after-donate, platform
    refusal) simply has no attribute — which is exactly the silent drop
    this rule exists to catch.
    """
    return lowered_text.count("tf.aliasing_output")


def check_donation(
    targets: Sequence[StepTarget], result: VerifyResult, baseline: Baseline
) -> None:
    lines = result.summary.setdefault("TM401", [])
    donating = [t for t in targets if t.donated_leaves > 0]
    if not donating:
        lines.append(
            "no target declares donation on this backend "
            "(CPU: engine declares none by design); nothing to audit"
        )
    for t in donating:
        result.checks += 1
        realized = aliased_output_count(t.lowered_text())
        lines.append(
            f"{t.name}: declared {t.donated_leaves} donated "
            f"leaves, lowered module aliases {realized}"
        )
        if realized < t.donated_leaves:
            result.add(baseline, Finding(
                "TM401", t.name,
                f"dropped:{realized}of{t.donated_leaves}",
                f"declares {t.donated_leaves} donated leaves but the "
                f"lowered module aliases only {realized} — donation was "
                f"silently dropped",
            ))
    # One representative compile proves the aliasing survives past
    # lowering into the executable (attributes can in principle be
    # discarded by the compiler); the trainer epoch step is the one
    # donating target on every backend.
    train = [t for t in donating if t.kind == "train"]
    if train:
        t = train[0]
        result.checks += 1
        compiled = t.traced.lower().compile()
        donate = tuple(getattr(compiled, "donate_argnums", ()) or ())
        aliased = "input_output_alias" in compiled.as_text()
        lines.append(
            f"{t.name}: compiled donate_argnums={donate}, "
            f"executable input_output_alias={'yes' if aliased else 'no'}"
        )
        if not donate or not aliased:
            result.add(baseline, Finding(
                "TM401", t.name, "compile:no-alias",
                "compiled executable shows no input_output_alias for the "
                "declared donation",
            ))


# ---------------------------------------------------------------------------
# TM402 — host-transfer freedom

#: Primitive names that imply a host round trip inside the jitted step.
#: ``device_put`` is NOT here: it appears benignly for weight constants
#: staged into the trace and does not stall dispatch.
_HOST_PRIM_EXACT = frozenset({"infeed", "outfeed", "outside_call", "debug_print"})
_HOST_PRIM_SUBSTRING = "callback"
#: Effects that only a host transfer carries (``jax.debug.print`` /
#: ``debug.callback`` carry the debug effect, ``io_callback`` the IO
#: one), matched by type name so a renamed primitive is still caught.
_HOST_EFFECTS = frozenset(
    {"DebugEffect", "OrderedDebugEffect", "IOEffect", "OrderedIOEffect"}
)


def iter_eqns(jaxpr) -> Iterator:
    """Every eqn in ``jaxpr`` and all nested sub-jaxprs (pjit bodies,
    scan/cond/while branches), depth-first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def _subjaxprs(v) -> List:
    if hasattr(v, "eqns"):          # open Jaxpr
        return [v]
    if hasattr(v, "jaxpr"):         # ClosedJaxpr
        return [v.jaxpr]
    if isinstance(v, (list, tuple)):
        out: List = []
        for e in v:
            out.extend(_subjaxprs(e))
        return out
    return []


def forbidden_primitives(jaxpr) -> List[str]:
    """Names of host-transfer primitives found anywhere in the jaxpr."""
    bad = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if (
            name in _HOST_PRIM_EXACT
            or _HOST_PRIM_SUBSTRING in name
            or any(type(e).__name__ in _HOST_EFFECTS for e in eqn.effects)
        ):
            bad.append(name)
    return bad


def check_host_transfers(
    targets: Sequence[StepTarget], result: VerifyResult, baseline: Baseline
) -> None:
    lines = result.summary.setdefault("TM402", [])
    serve = [t for t in targets if t.kind == "serve"]
    prims_seen = set()
    clean = 0
    for t in serve:
        result.checks += 1
        jx = t.jaxpr
        open_jaxpr = jx.jaxpr if hasattr(jx, "jaxpr") else jx
        for eqn in iter_eqns(open_jaxpr):
            prims_seen.add(eqn.primitive.name)
        bad = forbidden_primitives(open_jaxpr)
        if bad:
            result.add(baseline, Finding(
                "TM402", t.name, f"host:{','.join(sorted(set(bad)))}",
                f"serve-path jaxpr contains host-transfer primitives: "
                f"{sorted(set(bad))}",
            ))
        else:
            clean += 1
    lines.append(
        f"{clean}/{len(serve)} serve steps free of host-transfer "
        f"primitives"
    )
    lines.append(
        "primitive closure across all serve jaxprs: "
        + ", ".join(sorted(prims_seen))
    )


# ---------------------------------------------------------------------------
# TM403 — recompile-key audit


def audit_registry_path(
    path, spec, *, n_buckets: int, n_forms: int, cap: int
) -> Tuple[List[Finding], int]:
    """Findings + worst-case per-(path, form) cache cardinality for one
    path-like object (``name`` / ``tunable`` / ``ingress_spec`` /
    ``input_form`` / ``fallback`` attributes — tests pass synthetic
    stand-ins)."""
    findings: List[Finding] = []
    target = f"registry:{path.name}"

    tunable = path.tunable
    if not isinstance(tunable, tuple):
        findings.append(Finding(
            "TM403", target, "tunable:not-tuple",
            f"tunable is {type(tunable).__name__}, not a finite tuple — "
            f"cache cardinality is unbounded/unauditable",
        ))
        tunable = ()
    for i, cand in enumerate(tunable):
        ok_shape = isinstance(cand, tuple) and all(
            isinstance(p, tuple) and len(p) == 2 and isinstance(p[0], str)
            for p in cand
        )
        if not ok_shape:
            findings.append(Finding(
                "TM403", target, f"params:{i}:malformed",
                f"tunable[{i}] is not a ((name, value), ...) tuple: "
                f"{cand!r}",
            ))
            continue
        try:
            hash(cand)
        except TypeError:
            findings.append(Finding(
                "TM403", target, f"params:{i}:unhashable",
                f"tunable[{i}] is unhashable and would raise at dispatch "
                f"(jit static args must hash): {cand!r}",
            ))
    try:
        hash(path.ingress_spec(spec))
    except TypeError:
        findings.append(Finding(
            "TM403", target, "ingress:unhashable",
            "ingress_spec(spec) is unhashable; the raw step keys its jit "
            "cache on it",
        ))
    if getattr(path, "fallback", None) is not None:
        from repro.serve.paths import available_paths, get_path

        if path.fallback not in available_paths():
            findings.append(Finding(
                "TM403", target, "fallback:unregistered",
                f"fallback {path.fallback!r} is not a registered path",
            ))
        elif get_path(path.fallback).input_form != path.input_form:
            findings.append(Finding(
                "TM403", target, "fallback:form-mismatch",
                f"fallback {path.fallback!r} has a different input form; "
                f"substitution would change the conversion done per "
                f"request",
            ))
    cardinality = n_buckets * max(1, len(tunable))
    if cardinality > cap:
        findings.append(Finding(
            "TM403", target, f"cardinality:{cardinality}",
            f"worst-case jit-cache cardinality per (path, form) is "
            f"{cardinality} (= {n_buckets} buckets x {max(1, len(tunable))} "
            f"param sets) > cap {cap}",
        ))
    return findings, cardinality


def check_recompile_keys(
    vcfg: VerifyConfig, result: VerifyResult, baseline: Baseline
) -> None:
    from repro.serve.paths import available_paths, get_path
    from tools.tmverify.targets import tiny_config

    lines = result.summary.setdefault("TM403", [])
    spec = tiny_config().patch
    n_buckets = len(buckets_for(vcfg.engine_max_batch))
    total = 0
    for name in available_paths():
        result.checks += 1
        findings, card = audit_registry_path(
            get_path(name), spec,
            n_buckets=n_buckets, n_forms=2, cap=vcfg.cardinality_cap,
        )
        for f in findings:
            result.add(baseline, f)
        total += card * 2  # literals + raw forms
        lines.append(
            f"{name}: <= {card} cache keys per form "
            f"({n_buckets} buckets x {max(1, len(get_path(name).tunable))} "
            f"param sets), cap {vcfg.cardinality_cap}"
        )
    lines.append(
        f"whole-registry worst case across both forms: {total} compiled "
        f"steps at engine max_batch={vcfg.engine_max_batch}"
    )
