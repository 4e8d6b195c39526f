"""Per-bucket evaluation-path autotuner (ARCHITECTURE.md §Autotune).

The serving engine keys every executable on (model geometry, eval path,
static kernel parameters, request form, bucket).  Which point in that
space is fastest depends on geometry and backend in ways no heuristic
captures: at paper geometry the fused kernel's in-register class sums win
on TPU, while at tiny clause counts XLA's dense matmul beats everything;
a mostly-empty clause pool flips the balance toward the sparse paths.
Rather than hardcode a table, the autotuner *measures*: for each
(request form, bucket) it times every admissible (path, params) candidate
on zero-filled inputs of exactly the shapes serving will dispatch, and
records the winner in a :class:`TunedPlan`.

Contract (relied on by the engine and tests/test_autotune.py):

  * **Deterministic.** Candidate enumeration is sorted; measurements are
    memoized per process on the full static key (geometry, backend, mesh,
    sparsity shape, form, bucket, path, params), so re-registering the
    same model yields the *same* plan even though wall-clock timings
    jitter; ties break lexicographically on (path, params).
  * **Bit-identity is free.** Every candidate is a registered
    :class:`~repro.serve.paths.EvalPath`, and all registered paths are
    asserted bit-identical to ``kernels/ref.py`` — the tuner can never
    trade correctness for speed, so it never has to check outputs.
  * **Hashable + serializable.** A :class:`TunedPlan` is hashable (it
    rides on :class:`~repro.serve.servable.ServableModel` as jit-static
    metadata) and round-trips through JSON (``to_json``/``from_json``)
    so a tuned plan checkpoints alongside the model and restores without
    re-measuring.
  * **Admissibility.** Literal-form requests arrive already converted to
    the registered path's input form, so only same-form paths compete;
    raw-form requests own their ingress in-graph, so every path competes.
    Sparse paths that would resolve to their dense fallback (no sparsity
    analysis attached) are deduplicated away.  Non-default kernel
    parameter sets are swept only where the Pallas kernels actually
    compile (TPU backend, unmeshed).

The measured trajectory (winner + every candidate's time) is surfaced in
``ServeStats.autotune`` and in ``benchmarks/bench_serve.py`` rows.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.ingress import IngressSpec, raw_trailing_shape
from repro.serve import paths as sp
from repro.serve.servable import ServableModel, servable_digest

__all__ = [
    "TunedPlan",
    "AutotuneReport",
    "autotune_servable",
    "clear_measure_memo",
]

#: ((name, value), ...) static kernel parameters — see paths.Params.
Params = sp.Params

FORMS = ("literals", "raw")


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """The autotuner's decisions: (form, bucket) -> (path, params).

    ``entries`` is a sorted tuple of ``(form, bucket, path_name, params)``
    — pure strings/ints, so the plan is hashable and participates in jit
    static keys without ever forcing a recompile on re-measurement (the
    measured times live in :class:`AutotuneReport`, not here).

    ``digest`` records the :func:`~repro.serve.servable.servable_digest`
    of the register image the plan was measured on (lifecycle
    provenance: a plan carried across a hot swap is identifiable as
    tuned-for-a-prior-version).  ``""`` means unstamped — pre-lifecycle
    plans deserialize with it and stay bit-compatible.
    """

    entries: Tuple[Tuple[str, int, str, Params], ...] = ()
    digest: str = ""

    def lookup(self, form: str, bucket: int) -> Optional[Tuple[str, Params]]:
        """The tuned (path, params) for a dispatch, or None if untuned.

        Exact (form, bucket) match first; otherwise the nearest tuned
        bucket for the form (largest tuned <= bucket, else smallest
        tuned) — a bucket between tuned endpoints behaves like its
        closest measured neighbor rather than falling back to defaults.
        """
        best = None
        below, above = None, None
        for f, b, path, params in self.entries:
            if f != form:
                continue
            if b == bucket:
                return (path, params)
            if b < bucket and (below is None or b > below[0]):
                below = (b, path, params)
            if b > bucket and (above is None or b < above[0]):
                above = (b, path, params)
        pick = below or above
        return (pick[1], pick[2]) if pick else best

    def with_entry(
        self, form: str, bucket: int, path: str, params: Params
    ) -> "TunedPlan":
        kept = tuple(
            e for e in self.entries if not (e[0] == form and e[1] == bucket)
        )
        return TunedPlan(
            entries=tuple(sorted(kept + ((form, bucket, path, params),))),
            digest=self.digest,
        )

    def to_json(self) -> str:
        entries = [
            {"form": f, "bucket": b, "path": p, "params": [list(kv) for kv in ps]}
            for f, b, p, ps in self.entries
        ]
        if not self.digest:
            # Unstamped plans keep the legacy bare-list format so older
            # readers (and committed fixtures) stay byte-compatible.
            return json.dumps(entries)
        return json.dumps({"digest": self.digest, "entries": entries})

    @classmethod
    def from_json(cls, text: str) -> "TunedPlan":
        doc = json.loads(text)
        digest = ""
        if isinstance(doc, dict):        # stamped format
            digest = str(doc.get("digest", ""))
            doc = doc.get("entries", [])
        entries = tuple(
            sorted(
                (
                    e["form"],
                    int(e["bucket"]),
                    e["path"],
                    tuple((str(k), v) for k, v in e["params"]),
                )
                for e in doc
            )
        )
        return cls(entries=entries, digest=digest)


@dataclasses.dataclass
class AutotuneReport:
    """Everything the tuner measured (one row per (form, bucket))."""

    rows: List[Dict] = dataclasses.field(default_factory=list)
    total_s: float = 0.0

    def as_dict(self) -> Dict:
        return {"rows": list(self.rows), "total_s": self.total_s}


# Measurements memoized on the full static key so two register() calls in
# one process produce identical plans (wall clock jitters; the memo does
# not).  Cross-process determinism is what TunedPlan serialization is for.
_MEASURE_MEMO: Dict[Tuple, float] = {}


def clear_measure_memo() -> None:
    """Drop memoized timings (tests re-measuring on purpose)."""
    _MEASURE_MEMO.clear()


def _zero_input(
    servable: ServableModel, path: "sp.EvalPath", form: str,
    bucket: int, ingress: IngressSpec,
) -> np.ndarray:
    spec = servable.config.patch
    if form == "raw":
        return np.zeros((bucket,) + raw_trailing_shape(ingress), np.uint8)
    if path.input_form == sp.PACKED:
        return np.zeros((bucket, spec.n_patches, spec.n_words), np.uint32)
    return np.zeros((bucket, spec.n_patches, spec.n_literals), np.uint8)


def _candidates(
    servable: ServableModel,
    registered: "sp.EvalPath",
    form: str,
    *,
    sweep_params: bool,
) -> List[Tuple[str, Params]]:
    """Sorted, deduplicated (path, params) candidates for one form."""
    out: List[Tuple[str, Params]] = []
    seen = set()
    for name in sp.available_paths():
        path = sp.get_path(name)
        if form == "literals" and path.input_form != registered.input_form:
            continue
        resolved = sp.resolve_path(path, servable)
        if resolved is not path:
            continue    # would fall back: the fallback competes on its own
        psets = path.tunable if sweep_params else ((),)
        for params in psets:
            key = (name, params)
            if key not in seen:
                seen.add(key)
                out.append(key)
    return sorted(out)


def _time_candidate(step, *args, repeats: int) -> float:
    """Best-of-``repeats`` seconds per call (after one untimed warm call)."""
    jax.block_until_ready(step(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_servable(
    servable: ServableModel,
    path_name: str,
    ingress: IngressSpec,
    buckets: Sequence[int],
    forms: Sequence[str] = FORMS,
    *,
    repeats: int = 3,
    smesh=None,
    max_seconds: Optional[float] = None,
) -> Tuple[TunedPlan, AutotuneReport]:
    """Measure every admissible candidate per (form, bucket); return the
    winning :class:`TunedPlan` plus the full :class:`AutotuneReport`.

    ``smesh`` (a ServeMesh) measures through the meshed step the engine
    will actually dispatch, at default params only.  ``max_seconds``
    bounds wall clock: once exceeded, remaining candidates are skipped
    (the best-so-far still wins — noted in the report) and remaining
    (form, bucket) cells keep the registered path.  Leave it None for
    byte-reproducible plans.
    """
    # Engine-layer steps imported here (engine imports this module too).
    from repro.serve.engine import classify_raw_step, classify_step
    from repro.serve.mesh import classify_step_meshed

    backend = jax.default_backend()
    sweep = backend == "tpu" and smesh is None
    registered = sp.get_path(path_name)
    sparsity_key = None if servable.sparsity is None else servable.sparsity.n_active
    plan = servable.tuned or TunedPlan()
    report = AutotuneReport()
    t_start = time.perf_counter()
    budget_hit = False

    for form in forms:
        if form not in FORMS:
            raise ValueError(f"unknown autotune form {form!r} (use {FORMS})")
        for bucket in dict.fromkeys(int(b) for b in buckets):
            cands = _candidates(servable, registered, form, sweep_params=sweep)
            timed: List[Tuple[float, str, Params]] = []
            skipped = []
            for name, params in cands:
                if max_seconds is not None and (
                    time.perf_counter() - t_start > max_seconds
                ):
                    budget_hit = True
                if budget_hit and timed:
                    skipped.append(name)
                    continue
                memo_key = (
                    servable.config, backend, smesh, sparsity_key,
                    form, bucket, name, params,
                )
                if memo_key not in _MEASURE_MEMO:
                    arr = _zero_input(
                        servable, sp.get_path(name), form, bucket, ingress
                    )
                    if smesh is not None:
                        x = smesh.place_batch(arr)
                        step = lambda: classify_step_meshed(
                            servable, x, smesh=smesh, path_name=name,
                            ingress=ingress if form == "raw" else None,
                            params=params,
                        )
                    elif form == "raw":
                        x = arr
                        step = lambda: classify_raw_step(
                            servable, x, name, ingress, params=params
                        )
                    else:
                        x = arr
                        step = lambda: classify_step(
                            servable, x, name, params=params
                        )
                    _MEASURE_MEMO[memo_key] = _time_candidate(
                        step, repeats=repeats
                    )
                timed.append((_MEASURE_MEMO[memo_key], name, params))
            if not timed:
                continue
            # Deterministic winner: min time, ties by (path, params).
            best_t, best_name, best_params = min(
                timed, key=lambda t: (t[0], t[1], t[2])
            )
            plan = plan.with_entry(form, bucket, best_name, best_params)
            report.rows.append(
                {
                    "form": form,
                    "bucket": bucket,
                    "winner": best_name,
                    "params": [list(kv) for kv in best_params],
                    "us_per_call": best_t * 1e6,
                    "candidates": [
                        {
                            "path": n,
                            "params": [list(kv) for kv in ps],
                            "us_per_call": t * 1e6,
                        }
                        for t, n, ps in sorted(timed)
                    ],
                    "skipped": skipped,
                }
            )
    report.total_s = time.perf_counter() - t_start
    # Provenance stamp: the plan is tuned for THIS register image.  The
    # entries stay pure strings/ints; re-measuring the same image yields
    # the same digest, so determinism (and the jit static key) holds.
    plan = dataclasses.replace(plan, digest=servable_digest(servable))
    return plan, report
