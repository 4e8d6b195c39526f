"""Batched ConvCoTM serving engine.

The software counterpart of the chip's continuous classification mode
(Sec. IV-C): models are frozen once into :class:`ServableModel` register
images, registered under a dataset key (MNIST / Fashion-MNIST /
Kuzushiji-MNIST, ...), and request batches stream through a jitted
classify step.

Device-resident ingress
-----------------------
``classify`` accepts three request forms:

  * **raw** (the default): uint8 pixel batches ``[n, Y, X]``.  The whole
    raw -> booleanize -> patches -> literals -> pack -> class sums path
    runs as ONE jitted graph (:data:`classify_raw_step`, input buffer
    donated) — one H2D copy in, one D2H copy out, mirroring the ASIC
    where booleanized pixels stream straight into the clause datapath.
  * ``ingress='host'``: the legacy host-side pipeline
    (``data.pipeline.preprocess_for_serving``), kept as the baseline the
    device path is asserted bit-identical against.
  * ``preprocessed=True``: literals already in the path's input form
    (validated, then the literal-form :data:`classify_step`).

Batch bucketing
---------------
jit recompiles per input shape, so arbitrary request sizes would compile
without bound.  Requests are padded up to the nearest power-of-two bucket
(clamped to ``max_batch``) and results sliced back — at most
``log2(max_batch) + 1`` compilations per (model, path, request form)
ever, after which every request hits a warm executable.  Padding rows
(zero images / zero literal words) produce garbage predictions that are
sliced off and cannot perturb real rows (no cross-batch interaction in
the datapath).

Async dispatch
--------------
:meth:`ServingEngine.dispatch` submits a request and returns an
:class:`InFlightClassify` immediately — JAX dispatch is asynchronous, so
the device crunches batch k while the caller pads/dispatches batch k+1
(the ``ServingService`` worker does exactly this).  ``classify`` is
``dispatch(...).result()``.

Copy-back begins at launch: right after each chunk's step is launched,
the device-to-host copy of its ``preds`` and ``sums`` is queued behind
it (``copy_to_host_async``; on a mesh, of every addressable shard), so
the results cross to the host while later chunks are still being put,
launched and computed, and ``result()`` reads host copies that are in
flight or done.  ``result()`` always reads every output it was handed,
so this moves no byte that would not move anyway; a handle dropped
without ``result()`` wastes its copies.  :class:`ServeStats` counts
``copies_started`` (output arrays whose host copy began at launch) and
``copies_read`` (output arrays read by ``result()``); they are equal
once every dispatched request has been read.

Per-request latency is split at the host's own boundaries: ``ingress``
(validation, or the host pipeline on ``ingress='host'``), ``dispatch``
(padding, H2D put and the jitted step's launch over every chunk),
``wait`` (``block_until_ready``) and ``fetch`` (reading the host
copies, slicing and concatenation).  :class:`ServeStats` keeps a
histogram of each of the last three per model, and the same stages open
profiler spans (``serve.engine.*``, ARCHITECTURE.md §Telemetry), so a
trace shows which one the device waited on.  Throughput is compared
against the paper's 60.3k classifications/s (measured numbers in
EXPERIMENTS.md §Serve and §Ingress).

Multi-device serving
--------------------
Constructed with a :class:`~repro.serve.mesh.ServeMesh`, the engine
places each registered servable across the mesh (replicated, or
clause-sharded over the "model" axis) and shards every dispatched bucket
over the "data" axis — the same bucketed jit steps then execute one
program across all mesh devices and results gather on ``.result()``,
bit-identical to the single-device engine (``serve/mesh.py``,
ARCHITECTURE.md §ServeMesh).  Buckets are clamped from below to the
data-axis size so padding always splits evenly.

TM Composites
-------------
A composite (``core/composites.py``: several ConvCoTM specialists voting
on one frame, Table III) registers like a single bank, with one
booleanization per specialist.  Each member is frozen and analysed on
its own and gets its own :class:`IngressSpec`; one jitted step
(:func:`classify_composite_step`) runs every member's registered eval
path on its own ingress of the same raw buffer, each under
``jax.named_scope("specialist<k>")``, then the vote.  Its
``class_sums`` are the members' exact sums ``int32 [n, K, m]`` and its
``predictions`` the vote's argmax.  A composite serves raw frames on one
device; mesh placement, autotuning and host or literal request forms
raise an error that names it.

This is the synchronous library layer.  Online serving — request queue,
admission control, latency-aware microbatching across concurrent
submitters, multi-model fairness — lives one layer up in
:mod:`repro.serve.service` (``ServingService``).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clauses as cl
from repro.core.composites import CompositeConfig, CompositeModel, composite_vote
from repro.core.cotm import CoTMConfig, CoTMModel
from repro.core.ingress import IngressSpec, raw_trailing_shape
from repro.data.pipeline import preprocess_for_serving
from repro.serve.autotune import TunedPlan, autotune_servable
from repro.serve.faults import StepCompileError
from repro.serve.mesh import ServeMesh, classify_step_meshed
from repro.serve.paths import (
    PACKED,
    Params,
    folded_convolution,
    get_path,
    run_path,
    run_path_raw,
)
from repro.serve.servable import (
    CompositeServable,
    ServableModel,
    ServableVersion,
    analyze_sparsity,
    freeze,
    freeze_composite,
    servable_digest,
)
from repro.serve.telemetry import Histogram, span

__all__ = [
    "ClassifyResult",
    "InFlightClassify",
    "ServeStats",
    "ServingEngine",
    "classify_step",
    "classify_composite_step",
    "classify_raw_step",
    "composite_step_jit",
    "raw_step_jit",
]


@dataclasses.dataclass
class ClassifyResult:
    """One request's outcome."""

    predictions: np.ndarray   # int32 [n]
    class_sums: np.ndarray    # int32 [n, m]; a composite's [n, K, m] per member
    latency_s: float          # wall clock, dispatch() entry -> result in hand
    bucket: int               # largest padded batch size executed
    ingress_s: float = 0.0    # validation, or the host pipeline (ingress='host')
    dispatch_s: float = 0.0   # padding, H2D put and launch over every chunk
    wait_s: float = 0.0       # block_until_ready on the device results
    fetch_s: float = 0.0      # reading the host copies, slicing, concatenation
    version: int = 0          # monotonic id of the version that computed it


@dataclasses.dataclass
class ServeStats:
    """Running per-model accounting.

    ``devices`` is the mesh size the model serves on (1 unmeshed);
    buckets are *global* batch sizes — on a mesh each device executes
    ``bucket // data_shards`` rows (:attr:`per_device_bucket_hits`).

    ``dispatch`` is recorded once per ``dispatch()`` call (validation
    excluded), ``wait`` and ``fetch`` once per ``result()`` call, each on
    the thread that ran that stage; ``compiles`` counts first dispatches
    of a (form, bucket) on the installed image.  ``copies_started``
    counts output arrays whose host copy began at launch (warmup
    excluded), ``copies_read`` output arrays read by ``result()``.
    ``active_clauses`` holds the nonempty clauses of each bank the
    installed image serves (one per specialist of a composite), set at
    register, swap and rollback.  ``folded_checks`` counts bank-chunks
    whose clause check ran as the folded convolution
    (``core.clauses.eval_clauses_folded``; a composite chunk counts once
    per member that took it; warmup excluded).
    """

    requests: int = 0
    images: int = 0
    total_latency_s: float = 0.0
    dispatch: Histogram = dataclasses.field(default_factory=Histogram)
    wait: Histogram = dataclasses.field(default_factory=Histogram)
    fetch: Histogram = dataclasses.field(default_factory=Histogram)
    compiles: int = 0
    copies_started: int = 0
    copies_read: int = 0
    folded_checks: int = 0
    active_clauses: Tuple[int, ...] = ()
    bucket_hits: Dict[int, int] = dataclasses.field(default_factory=dict)
    compiled_buckets: Tuple[int, ...] = ()
    devices: int = 1                  # mesh size (1 = unmeshed)
    data_shards: int = 1              # batch shards over the "data" axis
    # Autotune outcome: {"rows": [...], "total_s": ..., "plan": [...]}
    # (see serve/autotune.py); empty dict when the model was not tuned.
    autotune: Dict = dataclasses.field(default_factory=dict)
    # Degradation state (ARCHITECTURE.md §Faults): the fallback path the
    # circuit breaker moved this model onto (None = registered path),
    # and how many degrade steps have been taken.
    fallback_path: Optional[str] = None
    degrade_steps: int = 0

    @property
    def classifications_per_s(self) -> float:
        return self.images / self.total_latency_s if self.total_latency_s else 0.0

    @property
    def mean_latency_us(self) -> float:
        return self.total_latency_s / self.requests * 1e6 if self.requests else 0.0

    @property
    def per_device_bucket_hits(self) -> Dict[int, int]:
        """Bucket hits keyed by the rows each device actually executed."""
        return {b // self.data_shards: h for b, h in self.bucket_hits.items()}

    def snapshot(self) -> "ServeStats":
        """A copy that shares no mutable state with the live counters."""
        return dataclasses.replace(
            self,
            dispatch=self.dispatch.copy(),
            wait=self.wait.copy(),
            fetch=self.fetch.copy(),
            bucket_hits=dict(self.bucket_hits),
            autotune=dict(self.autotune),
        )

    def as_dict(self) -> Dict:
        return {
            "requests": self.requests,
            "images": self.images,
            "classifications_per_s": self.classifications_per_s,
            "mean_latency_us": self.mean_latency_us,
            "dispatch": self.dispatch.summary(),
            "wait": self.wait.summary(),
            "fetch": self.fetch.summary(),
            "compiles": self.compiles,
            "copies_started": self.copies_started,
            "copies_read": self.copies_read,
            "folded_checks": self.folded_checks,
            "active_clauses": list(self.active_clauses),
            "bucket_hits": dict(self.bucket_hits),
            "compiled_buckets": list(self.compiled_buckets),
            "devices": self.devices,
            "data_shards": self.data_shards,
            "per_device_bucket_hits": dict(self.per_device_bucket_hits),
            "autotune": dict(self.autotune),
            "fallback_path": self.fallback_path,
            "degrade_steps": self.degrade_steps,
        }


@dataclasses.dataclass
class _Entry:
    servable: ServableModel
    booleanize_method: str
    booleanize_kw: Dict
    path_name: str
    ingress: IngressSpec
    stats: ServeStats
    # (form, bucket) pairs whose executable is warm; 'raw' and 'literals'
    # compile separately but share the user-visible compiled_buckets list.
    # Reset on swap/rollback: bucket warmth is per register image (the
    # sparsity shape can change between versions).
    compiled: set = dataclasses.field(default_factory=set)
    autotune: bool = False
    # Lifecycle stamp of the image currently installed, and the one-deep
    # history rollback() restores from (the whole placed image is kept,
    # so rollback is an O(1) pointer flip — no re-analysis, no H2D).
    version: ServableVersion = dataclasses.field(default_factory=ServableVersion)
    previous: Optional[Tuple[ServableModel, ServableVersion]] = None
    # Memo of the stamped image servable() hands out, so repeated reads of
    # an unchanged version return the identical object (pack-once contract).
    stamped: Optional[ServableModel] = None
    # One booleanization ({"method": ..., **knobs}) per specialist of a
    # composite, and the arguments its spans carry; empty for one bank.
    members_booleanize: Tuple[Dict, ...] = ()
    span_args: Dict = dataclasses.field(default_factory=dict)

    @property
    def composite(self) -> bool:
        return isinstance(self.servable, CompositeServable)

    def resolve(self, form: str, bucket: int) -> Tuple[str, Params]:
        """The (path, params) this entry dispatches for a (form, bucket):
        the tuned winner when a plan covers it, else the registered path
        at default params."""
        plan = self.servable.tuned
        if plan is not None:
            hit = plan.lookup(form, bucket)
            if hit is not None:
                return hit
        return self.path_name, ()


def _active_clauses(servable) -> Tuple[int, ...]:
    """The nonempty clauses of each bank ``servable`` serves."""
    members = getattr(servable, "members", (servable,))
    nonempty = jax.device_get([m.nonempty for m in members])    # one copy
    return tuple(map(int, (n.sum() for n in nonempty)))


def _map_banks(servable, fn):
    """``fn`` applied to the one bank, or to each member of a composite."""
    if isinstance(servable, CompositeServable):
        return dataclasses.replace(
            servable, members=tuple(fn(m) for m in servable.members)
        )
    return fn(servable)


def _folded_banks(servable, path_name: str) -> int:
    """How many of ``servable``'s banks a raw chunk on ``path_name``
    checks as the folded convolution."""
    members = getattr(servable, "members", (servable,))
    return sum(folded_convolution(path_name, m) for m in members)


def _raw_shape(ingress) -> Tuple[int, ...]:
    """Trailing dims of a raw batch; a composite's members share one."""
    if isinstance(ingress, tuple):
        ingress = ingress[0]
    return raw_trailing_shape(ingress)


def _ingress_for(
    path_name: str, servable, method: str, kw: Dict, members_booleanize
):
    """The registered ingress: one :class:`IngressSpec` for a single bank,
    a tuple of one per specialist for a composite."""
    path = get_path(path_name)
    if isinstance(servable, CompositeServable):
        return tuple(
            path.ingress_spec(m.config.patch, **b)
            for m, b in zip(servable.members, members_booleanize)
        )
    return path.ingress_spec(servable.config.patch, method=method, **kw)


#: Eval paths whose class sums take weights wider than int8 (the ones
#: built on ``core.clauses.class_sums``); the Pallas class-sum kernels and
#: their oracles take int8 weights only.
_WIDE_WEIGHT_PATHS = ("dense", "matmul", "bitpacked", "kernel", "sparse")


def _check_weight_width(path_name: str, servable) -> None:
    for m in getattr(servable, "members", (servable,)):
        bits = m.config.weight_bits
        if bits > 8 and path_name not in _WIDE_WEIGHT_PATHS:
            raise ValueError(
                f"eval path {path_name!r} serves int8 weights only; a "
                f"{bits}-bit configuration needs one of {_WIDE_WEIGHT_PATHS}"
            )


def _classify_step(
    servable: ServableModel, lits: jax.Array, path_name: str, params: Params = ()
):
    path = get_path(path_name)
    v = run_path(path, servable, lits, params)
    return cl.argmax_predict(v), v


#: The literal-form jitted classify step: (servable, literals, path_name
#: [, params]) -> (predictions, class_sums).  Module-level so every
#: engine instance (and ``train.serve_step.make_tm_serve_fn``) shares one
#: compile cache; jit keys on (bucket shape, model config, path, params)
#: — the bounded-recompile contract.
classify_step = jax.jit(_classify_step, static_argnames=("path_name", "params"))


def _classify_raw_step(
    servable: ServableModel,
    raw: jax.Array,
    path_name: str,
    ingress: IngressSpec,
    params: Params = (),
):
    path = get_path(path_name)
    v = run_path_raw(path, servable, raw, ingress, params)
    return cl.argmax_predict(v), v


#: Lazily built so jax.default_backend() (which initializes the backend)
#: is not forced at import time — importing repro.serve must not freeze
#: the platform choice before e.g. jax.config.update/distributed init.
_raw_step_jit = None


def raw_step_jit():
    """Build (once) and return the raw-form jitted step.

    The jit wrapper — and with it the donation decision — is built on
    first use, when the backend is actually resolved.  Exposed so
    ``tools/tmverify`` can audit the very wrapper dispatch uses (its
    ``donate_argnums`` and static keys) instead of a reconstruction.
    """
    global _raw_step_jit
    if _raw_step_jit is None:
        _raw_step_jit = jax.jit(
            _classify_raw_step,
            static_argnames=("path_name", "ingress", "params"),
            donate_argnums=() if jax.default_backend() == "cpu" else (1,),
        )
    return _raw_step_jit


def _classify_composite_step(
    servable: CompositeServable,
    raw: jax.Array,
    path_name: str,
    ingress: Tuple[IngressSpec, ...],
    params: Params = (),
):
    path = get_path(path_name)
    sums = []
    for k, (member, spec) in enumerate(zip(servable.members, ingress)):
        with jax.named_scope(f"specialist{k}"):
            sums.append(run_path_raw(path, member, raw, spec, params))
    sums = jnp.stack(sums, axis=1)                      # [B, K, m]
    preds, _ = composite_vote(sums)
    return preds, sums


_composite_step_jit = None


def composite_step_jit():
    """Build (once) and return the composite's jitted raw step, with the
    same static keys and donation as :func:`raw_step_jit`."""
    global _composite_step_jit
    if _composite_step_jit is None:
        _composite_step_jit = jax.jit(
            _classify_composite_step,
            static_argnames=("path_name", "ingress", "params"),
            donate_argnums=() if jax.default_backend() == "cpu" else (1,),
        )
    return _composite_step_jit


def classify_composite_step(
    servable, raw, path_name: str, ingress: Tuple[IngressSpec, ...], params: Params = ()
):
    """The composite's raw-form step: every member's ingress and eval
    path on the same raw ``uint8 [B, Y, X, Z]`` buffer (donated), then the
    vote, in one executable.  Returns (predictions ``[B]``, per-member
    class sums ``int32 [B, K, m]``)."""
    return composite_step_jit()(
        servable, raw, path_name=path_name, ingress=ingress, params=params
    )


def classify_raw_step(
    servable, raw, path_name: str, ingress: IngressSpec, params: Params = ()
):
    """The raw-form jitted classify step: the ENTIRE ingress (booleanize
    -> patches -> literals -> pack) plus clause evaluation and class sums
    in one executable.  The raw pixel buffer is donated where the backend
    supports it — after the single H2D copy the input storage is recycled
    inside the graph (on CPU donation is a no-op and only warns, so it is
    skipped).  jit keys on (bucket shape, model config, path, IngressSpec).
    """
    return raw_step_jit()(
        servable, raw, path_name=path_name, ingress=ingress, params=params
    )


class InFlightClassify:
    """A dispatched classify request whose device work may still be running.

    ``result()`` blocks until the device arrays are ready, slices off the
    bucket padding, records the request's stats and returns the
    :class:`ClassifyResult`; it is idempotent.  The ``wait`` and ``fetch``
    stages are timed (and spanned) on the thread that calls it.  The
    host copies of the outputs were started at launch, so ``fetch``
    mostly finds them done; a handle dropped without ``result()`` (a
    service shutdown, a failed request) has paid for one copy of its
    outputs that nobody reads.
    """

    def __init__(
        self,
        entry: _Entry,
        parts,
        n: int,
        marks: Tuple[float, float, float],
        version: int = 0,
    ):
        self._entry = entry
        self._parts = parts            # [(preds, sums, n_i, bucket)], lazy
        self._n = n
        # perf_counter at dispatch() entry, after validation, after launch.
        self._marks = marks
        # Version id captured atomically at dispatch: a swap after this
        # point cannot retroactively change which weights computed us.
        self.version = version
        self._result: Optional[ClassifyResult] = None

    def result(self) -> ClassifyResult:
        if self._result is not None:
            return self._result
        args = self._entry.span_args
        t_wait = time.perf_counter()
        with span("serve.engine.wait", **args):
            jax.block_until_ready([(p, s) for p, s, _, _ in self._parts])
        t_fetch = time.perf_counter()
        with span("serve.engine.fetch", **args):
            preds = np.concatenate([np.asarray(p)[:ni] for p, _, ni, _ in self._parts])
            sums = np.concatenate([np.asarray(s)[:ni] for _, s, ni, _ in self._parts])
        t_end = time.perf_counter()
        t0, t_ingress, t_launched = self._marks
        st = self._entry.stats
        st.requests += 1
        st.images += self._n
        st.total_latency_s += t_end - t0
        st.copies_read += 2 * len(self._parts)
        st.wait.record((t_fetch - t_wait) * 1e6)
        st.fetch.record((t_end - t_fetch) * 1e6)
        self._result = ClassifyResult(
            predictions=preds,
            class_sums=sums,
            latency_s=t_end - t0,
            bucket=max(b for _, _, _, b in self._parts),
            ingress_s=t_ingress - t0,
            dispatch_s=t_launched - t_ingress,
            wait_s=t_fetch - t_wait,
            fetch_s=t_end - t_fetch,
            version=self.version,
        )
        return self._result


class ServingEngine:
    """Multi-model batched classification service.

    ``mesh`` (a :class:`~repro.serve.mesh.ServeMesh`, or a bare
    ``jax.sharding.Mesh`` wrapped as a replicated ServeMesh) turns the
    engine multi-device: registered servables are placed across the mesh
    and every dispatched bucket is sharded over its "data" axis — one
    program across all devices, one gathered result, bit-identical to
    the single-device engine (see ``serve/mesh.py``).  The data-axis
    size must be a power of two <= ``max_batch`` so every pow2 bucket
    splits evenly.
    """

    def __init__(
        self,
        max_batch: int = 256,
        mesh: Optional[ServeMesh] = None,
        *,
        autotune: bool = False,
        autotune_repeats: int = 3,
        autotune_max_seconds: Optional[float] = None,
        faults=None,
    ):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if mesh is not None and not isinstance(mesh, ServeMesh):
            mesh = ServeMesh(mesh)
        if mesh is not None:
            nd = mesh.n_data
            if nd & (nd - 1):
                raise ValueError(
                    f'"data" axis size {nd} must be a power of two so pow2 '
                    f"buckets split evenly"
                )
            if nd > max_batch:
                raise ValueError(
                    f'"data" axis size {nd} exceeds max_batch={max_batch}'
                )
        self.max_batch = max_batch
        self.mesh = mesh
        # Optional FaultPlan (serve/faults.py): its on_engine_dispatch
        # seam runs at the top of every dispatch, so chaos tests can
        # inject engine failures mid-microbatch deterministically.
        self.faults = faults
        self.autotune_default = autotune
        self.autotune_repeats = autotune_repeats
        self.autotune_max_seconds = autotune_max_seconds
        self._servables: Dict[str, _Entry] = {}
        # Serializes entry mutation (swap/rollback/autotune) against
        # dispatch: a dispatch captures (servable, version) atomically,
        # so already-submitted microbatches complete on the old image
        # while new dispatches see the new one.  Re-entrant so the
        # service can pin one version across a multi-form microbatch
        # (``swap_guard``) around its own ``dispatch`` calls.
        self._lock = threading.RLock()

    @property
    def devices(self) -> int:
        """Mesh size (1 for the single-device engine)."""
        return 1 if self.mesh is None else self.mesh.devices

    @property
    def data_shards(self) -> int:
        """Batch shards per dispatched bucket (the "data" axis size)."""
        return 1 if self.mesh is None else self.mesh.n_data

    # --- registry ---------------------------------------------------------

    def _stamp(
        self,
        servable: ServableModel,
        source: Optional[ServableVersion],
        version_id: int,
    ) -> ServableVersion:
        """Engine-assigned monotonic id + provenance from ``source``
        (an explicit stamp, or the one riding on the servable); the
        content digest is computed when the source carries none."""
        return ServableVersion(
            version=version_id,
            epoch=source.epoch if source else 0,
            step=source.step if source else 0,
            digest=(
                source.digest
                if source and source.digest
                else servable_digest(servable)
            ),
        )

    def register(
        self,
        name: str,
        model: CoTMModel | ServableModel | CompositeModel | CompositeServable,
        config: Optional[CoTMConfig | CompositeConfig] = None,
        *,
        booleanize_method: str = "threshold",
        path: Optional[str] = None,
        booleanize_kw: Optional[Dict] = None,
        booleanize: Optional[Tuple[Dict, ...]] = None,
        autotune: Optional[bool] = None,
        tuned: Optional[TunedPlan] = None,
        version: Optional[ServableVersion] = None,
    ) -> ServableModel | CompositeServable:
        """Freeze (if needed) and register a model under a dataset key.

        Freezing happens here, exactly once — ``classify`` reuses the
        cached ``ServableModel`` arrays for every subsequent batch, and
        the freeze-time sparsity analysis (active-clause image, see
        ``serve/servable.py``) is attached here so the sparse eval paths
        are available.  The model's :class:`IngressSpec` (booleanize
        method + knobs, literal form of the eval path) is also fixed
        here; it is the static key of the raw-form classify executable.

        A composite (a ``CompositeModel`` with its ``CompositeConfig``, or
        a ``CompositeServable``) takes ``booleanize``, one dict
        ``{"method": ..., **knobs}`` per specialist, in place of
        ``booleanize_method``/``booleanize_kw``: each member is frozen and
        analysed on its own and gets its own ingress of the same raw
        frame.  It serves on one device and is not autotuned.

        ``autotune`` (default: the engine's ``autotune`` flag) arms the
        per-bucket path autotuner — it runs at :meth:`warmup` (or via
        :meth:`autotune` directly), never per request.  ``tuned``
        attaches a previously measured :class:`TunedPlan` (e.g. restored
        alongside a checkpoint) without re-measuring.

        ``version`` (or a stamp already riding on a ``ServableModel``)
        supplies lifecycle provenance (epoch/step/digest); the monotonic
        id itself is engine-assigned — 1 for a fresh slot, and a
        re-register of a live slot continues its id sequence like a
        :meth:`swap` would.  The dispatched image is stamp-stripped so
        version churn never touches jit cache keys.
        """
        autotune = self.autotune_default if autotune is None else autotune
        members_b: Tuple[Dict, ...] = ()
        if isinstance(model, (CompositeModel, CompositeServable)):
            servable = (
                model if isinstance(model, CompositeServable)
                else self._freeze_composite(name, model, config)
            )
            k = len(servable.members)
            if self.mesh is not None:
                raise ValueError(
                    f"{name!r} is a composite of {k} specialists, which is "
                    f"not served on a ServeMesh; register it on an engine "
                    f"without a mesh"
                )
            if autotune or tuned is not None:
                raise ValueError(
                    f"{name!r} is a composite of {k} specialists, which is "
                    f"not autotuned; register it with autotune=False"
                )
            if booleanize is None or len(booleanize) != k:
                raise ValueError(
                    f"{name!r} is a composite of {k} specialists: booleanize= "
                    f"takes one dict per specialist"
                )
            members_b = tuple(dict(b) for b in booleanize)
        else:
            if booleanize is not None:
                raise ValueError(
                    "booleanize= is for composites; a single bank takes "
                    "booleanize_method= and booleanize_kw="
                )
            if isinstance(model, ServableModel):
                servable = model
            else:
                if config is None:
                    raise ValueError("config required when registering a CoTMModel")
                servable = freeze(model, config)
        first = getattr(servable, "members", (servable,))[0]
        path_name = path or first.config.eval_path
        get_path(path_name)  # fail fast on unknown paths
        _check_weight_width(path_name, servable)
        booleanize_kw = dict(booleanize_kw or {})
        ingress = _ingress_for(
            path_name, servable, booleanize_method, booleanize_kw, members_b
        )
        if members_b:
            shapes = sorted({raw_trailing_shape(s) for s in ingress})
            if len(shapes) != 1:
                raise ValueError(
                    f"composite {name!r}: its specialists take raw frames of "
                    f"shapes {shapes}; the members must share one frame"
                )
        source = version if version is not None else servable.version
        # Freeze-time sparsity analysis (skipped on clause-sharded meshes,
        # where the active set is not shard-uniform and placement drops it
        # anyway — sparse paths then resolve to their dense fallbacks).
        if self.mesh is None or not self.mesh.shard_clauses:
            servable = _map_banks(servable, analyze_sparsity)
        if tuned is not None:
            servable = dataclasses.replace(servable, tuned=tuned)
        stamp = self._stamp(servable, source, self._next_version_id(name))
        servable = dataclasses.replace(servable, version=None)
        active = _active_clauses(servable)
        if self.mesh is not None:
            # Placement happens once, here: replicated register image or
            # clause-sharded splits (validates n_clauses divisibility).
            servable = self.mesh.place_servable(servable)
        with self._lock:
            self._servables[name] = _Entry(
                servable=servable,
                booleanize_method=booleanize_method,
                booleanize_kw=booleanize_kw,
                path_name=path_name,
                ingress=ingress,
                stats=ServeStats(
                    devices=self.devices,
                    data_shards=self.data_shards,
                    active_clauses=active,
                ),
                autotune=autotune,
                version=stamp,
                members_booleanize=members_b,
                span_args={"specialists": len(members_b)} if members_b else {},
            )
        return servable

    @staticmethod
    def _freeze_composite(name: str, model: CompositeModel, config) -> CompositeServable:
        if not isinstance(config, CompositeConfig):
            raise ValueError(
                f"composite {name!r}: a CompositeModel needs its CompositeConfig"
            )
        return freeze_composite(model, config)

    def _next_version_id(self, name: str) -> int:
        prev = self._servables.get(name)
        return prev.version.version + 1 if prev is not None else 1

    def load_checkpoint(
        self,
        name: str,
        directory: str,
        config: CoTMConfig,
        *,
        step: Optional[int] = None,
        booleanize_method: str = "threshold",
        path: Optional[str] = None,
    ) -> ServableModel:
        """Restore a trained model from ``checkpoint/`` and register it.

        Handles both checkpoint flavors: raw ``CoTMModel`` trees written
        by the training loop, and stamped register images written by
        :func:`~repro.checkpoint.checkpointer.save_servable` (the
        lifecycle driver's promote path) — the manifest's leaf names say
        which restore applies, so ``--ckpt-dir`` works on either."""
        import json
        import os

        from repro.checkpoint.checkpointer import (
            latest_step,
            restore_pytree,
            restore_servable,
        )

        resolved = latest_step(directory) if step is None else step
        if resolved is None:
            raise FileNotFoundError(f"no committed checkpoint under {directory}")
        manifest = os.path.join(
            directory, f"step_{resolved:08d}", "manifest.json"
        )
        with open(manifest) as f:
            leaves = json.load(f).get("leaves", {})
        if "include" in leaves and ".ta_state" not in leaves:
            servable, _ = restore_servable(config, directory, resolved)
            # Stamp provenance + TunedPlan ride on the servable itself.
            return self.register(
                name, servable,
                booleanize_method=booleanize_method, path=path,
            )
        template = CoTMModel(
            ta_state=jnp.zeros((config.n_clauses, config.n_literals), jnp.uint8),
            weights=jnp.zeros((config.n_classes, config.n_clauses), jnp.int32),
        )
        model, _, extra = restore_pytree(template, directory, resolved)
        extra = extra or {}
        stamp = ServableVersion.from_dict(extra.get("servable_version"))
        tuned = None
        if extra.get("tuned_plan"):
            tuned = TunedPlan.from_json(extra["tuned_plan"])
        return self.register(
            name, model, config, booleanize_method=booleanize_method, path=path,
            tuned=tuned, version=stamp if stamp != ServableVersion() else None,
        )

    def models(self) -> Tuple[str, ...]:
        return tuple(sorted(self._servables))

    def stats(self, name: str) -> ServeStats:
        """A snapshot of one model's stats (no alias of the live counters)."""
        return self._servables[name].stats.snapshot()

    def ingress_spec(self, name: str) -> IngressSpec:
        """The registered model's raw-form ingress description."""
        return self._servables[name].ingress

    def servable(self, name: str) -> ServableModel:
        """The frozen (and possibly placed) register image being served.

        Re-stamped with the entry's live :class:`ServableVersion` — the
        dispatched image itself is kept stamp-free (see :meth:`register`),
        so the stamp is attached on the way out for checkpointing and
        hand-offs.  Memoized per install: repeated reads of an unchanged
        version return the identical object."""
        with self._lock:
            entry = self._servables[name]
            if (
                entry.stamped is None
                or entry.stamped.version is not entry.version
            ):
                entry.stamped = dataclasses.replace(
                    entry.servable, version=entry.version
                )
            return entry.stamped

    def version(self, name: str) -> ServableVersion:
        """The lifecycle stamp of the version currently being served."""
        return self._servables[name].version

    def version_id(self, name: str) -> int:
        """Monotonic id of the version currently being served."""
        return self._servables[name].version.version

    def resolved_path(self, name: str, form: str, bucket: int) -> Tuple[str, Params]:
        """The (path, params) a (form, bucket) dispatch would actually
        evaluate: the tuned winner (or the registered path), with sparse
        paths resolved to their dense fallback when the servable carries
        no sparsity analysis.  Benchmarks use this to label rows with the
        path that really ran."""
        entry = self._servables[name]
        path_name, params = entry.resolve(form, self.bucket_for(bucket))
        resolved = get_path(path_name)
        from repro.serve.paths import resolve_path

        bank = getattr(entry.servable, "members", (entry.servable,))[0]
        final = resolve_path(resolved, bank)
        return final.name, (params if final is resolved else ())

    # --- lifecycle (ARCHITECTURE.md §Lifecycle) ---------------------------

    def swap_guard(self):
        """The engine lock, for callers that must pin ONE version across
        several ``dispatch`` calls (the service holds it around a multi-
        form-group microbatch so no microbatch spans two versions).
        Re-entrant with dispatch's own locking."""
        return self._lock

    def swap(
        self,
        name: str,
        model: CoTMModel | ServableModel | CompositeModel | CompositeServable,
        config: Optional[CoTMConfig | CompositeConfig] = None,
        *,
        version: Optional[ServableVersion] = None,
        tuned: Optional[TunedPlan] = None,
        retune: bool = False,
    ) -> ServableVersion:
        """Atomically replace ``name``'s weights under live load.

        The new image inherits the slot's serving contract — eval path,
        ingress spec, booleanize knobs, mesh placement — so only the
        weights change.  In-flight microbatches hold references to the
        old placed arrays and complete on the old version; dispatches
        entering after the install see the new one; nothing is dropped.

        Compiles only the delta: the dispatched image is stamp-stripped
        (version is never a jit key), geometry must match the live
        config, and the candidate's sparsity analysis is padded to
        :func:`~repro.serve.servable.active_pad` bins so swap storms
        re-use warm executables instead of compiling one shape per
        trained version.  A swap whose padded active count lands in an
        already-served bin compiles nothing (asserted with
        ``tools/recompile_guard.py`` in tests/test_lifecycle.py).

        ``tuned`` pins a plan measured for the candidate; by default the
        live version's plan is carried over (its ``digest`` marks it as
        tuned-for-a-prior-version); ``retune=True`` re-measures on the
        candidate instead.  Returns the freshly installed stamp; the
        displaced version is retained whole for :meth:`rollback`.

        A composite slot takes a composite of the same ``CompositeConfig``
        (every specialist's weights are replaced, each analysed on its
        own); it carries no plan, so ``tuned`` and ``retune`` are refused.
        """
        entry = self._servables[name]   # KeyError for unknown slots
        incoming = isinstance(model, (CompositeModel, CompositeServable))
        if entry.composite or incoming:
            if not (entry.composite and incoming):
                raise ValueError(
                    f"swap({name!r}): a composite and a single bank do not "
                    f"swap for each other (re-register the slot)"
                )
            if tuned is not None or retune:
                raise ValueError(
                    f"swap({name!r}): a composite is not autotuned"
                )
            candidate = (
                model if isinstance(model, CompositeServable)
                else self._freeze_composite(name, model, config)
            )
        elif isinstance(model, ServableModel):
            candidate = model
        else:
            if config is None:
                raise ValueError("config required when swapping in a CoTMModel")
            candidate = freeze(model, config)
        live_cfg = entry.servable.config
        if candidate.config != live_cfg:
            kind = "composite " if entry.composite else ""
            raise ValueError(
                f"swap({name!r}) {kind}config mismatch: a swap replaces "
                f"weights only — got {candidate.config!r}, serving "
                f"{live_cfg!r} (re-register for a geometry change)"
            )
        source = version if version is not None else candidate.version
        candidate = _map_banks(
            candidate, lambda m: dataclasses.replace(m, sparsity=None)
        )
        if self.mesh is None or not self.mesh.shard_clauses:
            # Per-version sparsity analysis (never cached across swaps —
            # the active set belongs to the weights), padded to pow2 bins
            # so the analysis *shape* is shared across versions.
            candidate = _map_banks(
                candidate, lambda m: analyze_sparsity(m, pad_to="pow2")
            )
        stamp = self._stamp(candidate, source, self._next_version_id(name))
        if entry.composite:
            candidate = dataclasses.replace(candidate, version=None)
        else:
            carried = entry.servable.tuned if tuned is None and not retune else tuned
            candidate = dataclasses.replace(
                candidate, tuned=carried, version=None
            )
        active = _active_clauses(candidate)
        if self.mesh is not None:
            candidate = self.mesh.place_servable(candidate)
        with self._lock:
            entry.previous = (entry.servable, entry.version)
            entry.servable = candidate
            entry.version = stamp
            entry.stats.active_clauses = active
            # Bucket warmth is per register image: the sparsity bin may
            # differ, so let compile accounting re-observe what actually
            # compiles (usually nothing — shapes are shared).
            entry.compiled = set()
        if retune:
            self.autotune(name)
        return stamp

    def rollback(self, name: str) -> ServableVersion:
        """Instantly restore the version displaced by the last swap.

        O(1): the previous placed image was retained whole, so no
        re-freeze, no sparsity re-analysis, no H2D transfer and no
        compile happen here.  The restored weights get a FRESH monotonic
        id (ids never regress) carrying the prior version's digest /
        epoch / step — the digest is what identifies the weights.
        A second rollback undoes the first (the pair flips back).
        """
        entry = self._servables[name]
        with self._lock:
            if entry.previous is None:
                raise ValueError(
                    f"rollback({name!r}): no previous version (nothing "
                    f"was swapped)"
                )
            prev_servable, prev_stamp = entry.previous
            entry.previous = (entry.servable, entry.version)
            entry.servable = prev_servable
            entry.stats.active_clauses = _active_clauses(prev_servable)
            entry.version = ServableVersion(
                version=entry.version.version + 1,
                epoch=prev_stamp.epoch,
                step=prev_stamp.step,
                digest=prev_stamp.digest,
            )
            entry.compiled = set()
            return entry.version

    # --- degraded modes (ARCHITECTURE.md §Faults) -------------------------

    def degrade_path(self, name: str) -> Optional[str]:
        """Move ``name`` one step down the degradation chain.

        Called by the service's circuit breaker after repeated dispatch
        failures on the current path: the entry's eval path falls back
        along :func:`repro.serve.paths.degraded_fallback` (sparse ->
        dense twin, fused -> matmul, ... -> dense) and its ingress spec
        is rebuilt for the fallback's literal form.  The tuned plan is
        dropped (its winners belong to the failing path) and bucket
        warmth resets — correctness over speed is the whole point of the
        degraded mode.  Outputs stay bit-identical to ``kernels/ref.py``
        by the multi-path equivalence contract.  Returns the new path
        name, or None when already at the bottom of the chain.
        """
        from repro.serve.paths import degraded_fallback

        entry = self._servables[name]
        with self._lock:
            nxt = degraded_fallback(entry.path_name)
            if nxt is None:
                return None
            entry.path_name = nxt
            entry.ingress = _ingress_for(
                nxt, entry.servable, entry.booleanize_method,
                entry.booleanize_kw, entry.members_booleanize,
            )
            if not entry.composite:
                entry.servable = dataclasses.replace(entry.servable, tuned=None)
            entry.compiled = set()
            entry.stats.fallback_path = nxt
            entry.stats.degrade_steps += 1
            return nxt

    def shrink_mesh(self) -> Optional[ServeMesh]:
        """Re-place every registered servable on a shrunk mesh after a
        device loss on the data axis.

        Halves the batch-shard count (model axis kept — clause shards
        hold model state; the data axis holds only request rows, so it
        is the one that can shed devices without re-freezing anything)
        and re-places each entry's register image via
        ``ServeMesh.place_servable`` — an O(model-size) device_put, no
        re-freeze, no sparsity re-analysis.  In-flight dispatches hold
        references to the old placed arrays and complete on the old
        mesh; the engine lock makes the cutover atomic, the same
        discipline as :meth:`swap`.  Bucket warmth resets (bucket
        shardings changed).  Returns the new mesh, or None when there is
        nothing to shrink (unmeshed, or data axis already 1).
        """
        with self._lock:
            if self.mesh is None:
                return None
            new = self.mesh.shrunk()
            if new is None:
                return None
            self.mesh = new
            for entry in self._servables.values():
                entry.servable = new.place_servable(entry.servable)
                entry.compiled = set()
                entry.stamped = None
                entry.stats.devices = new.devices
                entry.stats.data_shards = new.n_data
            return new

    # --- serving ----------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest power-of-two >= n, clamped to ``max_batch``.

        On a mesh, additionally clamped from below to the data-axis size
        so the padded batch always divides evenly over the batch shards
        (jit input shardings require exact divisibility).
        """
        if n < 1:
            raise ValueError("empty request")
        bucket = min(1 << (n - 1).bit_length(), self.max_batch)
        return max(bucket, self.data_shards)

    def autotune(
        self,
        name: str,
        buckets=None,
        *,
        forms=("literals", "raw"),
        repeats: Optional[int] = None,
        max_seconds: Optional[float] = None,
    ) -> TunedPlan:
        """Measure eval-path candidates per (form, bucket) and pin the
        winners on the registered servable (see ``serve/autotune.py``).

        Default buckets: the engine's bucket range endpoints
        (``bucket_for(1)`` and ``max_batch``) — :class:`TunedPlan` lookup
        maps intermediate buckets to their nearest tuned neighbor, so the
        endpoints cover the whole range at a fraction of the sweep cost;
        pass an explicit list to tune every bucket a workload hits.  The
        winning plan and the full measurement report land in
        :class:`ServeStats` (``stats.autotune``); the plan also rides on
        the servable (``servable(name).tuned``) for checkpointing.
        """
        entry = self._servables[name]
        if entry.composite:
            raise ValueError(
                f"{name!r} is a composite of {len(entry.servable.members)} "
                f"specialists, which is not autotuned"
            )
        if buckets is None:
            buckets = dict.fromkeys((self.bucket_for(1), self.max_batch))
        buckets = [self.bucket_for(int(b)) for b in buckets]
        plan, report = autotune_servable(
            entry.servable,
            entry.path_name,
            entry.ingress,
            buckets,
            forms,
            repeats=self.autotune_repeats if repeats is None else repeats,
            smesh=self.mesh,
            max_seconds=(
                self.autotune_max_seconds if max_seconds is None else max_seconds
            ),
        )
        with self._lock:
            entry.servable = dataclasses.replace(entry.servable, tuned=plan)
        entry.stats.autotune = {
            **report.as_dict(),
            "plan": [list(e) for e in plan.entries],
        }
        return plan

    def warmup(
        self, name: str, buckets=None, *, forms=None
    ) -> Tuple[int, ...]:
        """Pre-compile buckets so request latency excludes jit compiles.

        By default warms BOTH request forms per bucket — the raw-form
        fused graph (ingress + eval) and the literal-form step — since
        they compile separately (a composite has the raw form only); single-form workloads can pass
        ``forms=('raw',)`` or ``('literals',)`` to skip the other half's
        compile cost.  Default buckets: every power-of-two up to
        ``max_batch``.  Sizes are normalized through :meth:`bucket_for`
        first, so ``buckets=[10]`` compiles (and reports) bucket 16.
        Only compile accounting is touched — request/latency/hit stats
        stay clean.  Returns the buckets newly compiled, in order.

        Models registered with ``autotune=True`` are tuned here first
        (once), so warmup compiles exactly the executables dispatch will
        hit — each bucket's *tuned* path, in both forms.  Dispatching any
        (form, bucket) the default warmup covered then never recompiles
        (the no-recompile contract, tests/test_autotune.py).
        """
        entry = self._servables[name]
        if forms is None:
            forms = ("raw",) if entry.composite else ("literals", "raw")
        if unknown := set(forms) - {"literals", "raw"}:
            raise ValueError(f"unknown warmup forms: {sorted(unknown)}")
        if entry.composite and "literals" in forms:
            raise ValueError(f"composite {name!r} serves raw frames only")
        if entry.autotune and entry.servable.tuned is None:
            self.autotune(name, forms=forms)
        if buckets is None:
            buckets = []
            b = 1
            while b < self.max_batch:
                buckets.append(b)
                b <<= 1
            buckets.append(self.max_batch)
        for b in buckets:
            if not 1 <= b <= self.max_batch:
                raise ValueError(
                    f"warmup bucket {b} outside [1, max_batch={self.max_batch}]"
                )
        compiled = []
        for b in dict.fromkeys(self.bucket_for(b) for b in buckets):
            fresh = False
            zeros_for = {"literals": self._zero_literals, "raw": self._zero_raw}
            for form, zeros in ((f, zeros_for[f]) for f in forms):
                if (form, b) in entry.compiled:
                    continue
                preds, sums, _, _ = self._submit_bucket(
                    entry, zeros(entry, b), form, record_hit=False
                )
                jax.block_until_ready([preds, sums])
                fresh = True
            if fresh:
                compiled.append(b)
        return tuple(compiled)

    def _zero_literals(self, entry: _Entry, b: int) -> np.ndarray:
        spec = entry.servable.config.patch
        if get_path(entry.path_name).input_form == PACKED:
            return np.zeros((b, spec.n_patches, spec.n_words), np.uint32)
        return np.zeros((b, spec.n_patches, spec.n_literals), np.uint8)

    def _zero_raw(self, entry: _Entry, b: int) -> np.ndarray:
        return np.zeros((b,) + _raw_shape(entry.ingress), np.uint8)

    def _submit_bucket(
        self, entry: _Entry, arr: np.ndarray, form: str, record_hit: bool = True
    ):
        """Pad one <= max_batch chunk to its bucket, put it on the device(s),
        launch the jitted step and start the outputs' host copy, all
        WITHOUT blocking; returns ``(preds, sums, n, bucket)`` with lazy
        device arrays.  Records bucket hit/compile accounting."""
        n = arr.shape[0]
        bucket = self.bucket_for(n)
        args = entry.span_args
        with span("serve.engine.pad", **args):
            if bucket != n:
                pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
                arr = np.concatenate([arr, pad], axis=0)
        # The autotuned winner for this (form, bucket), or the registered
        # path at defaults.  Literal-form winners share the registered
        # path's input form (autotune admissibility), so ``arr`` is
        # always in the right form already.
        path_name, params = entry.resolve(form, bucket)
        # The first dispatch of a (form, bucket) traces and compiles its
        # step; a failure there is a defect on this backend (a Pallas
        # kernel Mosaic refuses), raised as such and never degraded around.
        fresh = (form, bucket) not in entry.compiled
        try:
            with span("serve.engine.put", **args):
                # One H2D copy; on a mesh one placed (data-sharded) buffer,
                # and nothing gathers until .result() reads the output.
                if self.mesh is not None:
                    x = self.mesh.place_batch(arr)
                else:
                    x = jax.device_put(arr)
            with span(
                "serve.engine.compile" if fresh else "serve.engine.launch",
                bucket=bucket, form=form, **args,
            ):
                if entry.composite:
                    preds, sums = classify_composite_step(
                        entry.servable, x, path_name, entry.ingress, params
                    )
                elif self.mesh is not None:
                    preds, sums = classify_step_meshed(
                        entry.servable, x,
                        smesh=self.mesh,
                        path_name=path_name,
                        ingress=entry.ingress if form == "raw" else None,
                        params=params,
                    )
                elif form == "raw":
                    preds, sums = classify_raw_step(
                        entry.servable, x, path_name, entry.ingress, params
                    )
                else:
                    preds, sums = classify_step(
                        entry.servable, x, path_name, params=params
                    )
        except Exception as e:
            if not fresh:
                raise
            raise StepCompileError(
                f"{path_name!r} {form} step for bucket {bucket} failed to "
                f"compile on {jax.default_backend()}: {e}"
            ) from e
        # Queued behind the step, so the copy-back overlaps the chunks
        # launched after this one.
        preds.copy_to_host_async()
        sums.copy_to_host_async()
        st = entry.stats
        if record_hit:
            st.bucket_hits[bucket] = st.bucket_hits.get(bucket, 0) + 1
            st.copies_started += 2
            if form == "raw":
                st.folded_checks += _folded_banks(entry.servable, path_name)
        if fresh:
            st.compiles += 1
            entry.compiled.add((form, bucket))
        if bucket not in st.compiled_buckets:
            st.compiled_buckets = st.compiled_buckets + (bucket,)
        return preds, sums, n, bucket

    def _validate_preprocessed(self, lits: np.ndarray, path, spec) -> None:
        """Reject wrong-form preprocessed literals instead of serving garbage.

        ``preprocessed=True`` requests must already be in the path's input
        form: dense uint8 ``[n, P, 2o]`` or packed uint32 ``[n, P, W]``.
        A dense array fed to a packed path (or vice versa) would silently
        produce garbage predictions — the dtypes happen to broadcast.
        """
        if path.input_form == PACKED:
            want_dtype, want_trail, form = (
                np.uint32, (spec.n_patches, spec.n_words),
                f"packed uint32 [n, P={spec.n_patches}, W={spec.n_words}]",
            )
        else:
            want_dtype, want_trail, form = (
                np.uint8, (spec.n_patches, spec.n_literals),
                f"dense uint8 [n, P={spec.n_patches}, 2o={spec.n_literals}]",
            )
        if lits.ndim != 3 or lits.shape[1:] != want_trail or lits.dtype != want_dtype:
            raise ValueError(
                f"preprocessed literals for eval path {path.name!r} must be "
                f"{form}; got {lits.dtype} {list(lits.shape)} "
                f"(use data.pipeline.preprocess_for_serving(..., "
                f"packed={path.input_form == PACKED}))"
            )

    def validate_raw(self, name: str, raw_images: np.ndarray) -> np.ndarray:
        """Check a raw pixel batch against the model's ingress geometry.

        Raises KeyError for unknown models and ValueError for empty or
        wrongly shaped requests; returns the batch as an ndarray.  Cheap —
        this is all the host-side work a raw request pays before the
        device graph.
        """
        entry = self._servables[name]
        raw = np.asarray(raw_images)
        if len(raw) == 0:
            raise ValueError("empty request")
        want = _raw_shape(entry.ingress)
        if raw.shape[1:] != want:
            method = (
                [b.get("method") for b in entry.members_booleanize]
                if entry.composite else entry.booleanize_method
            )
            raise ValueError(
                f"raw images for {name!r} must be [n, {', '.join(map(str, want))}] "
                f"(method={method!r}); got {list(raw.shape)}"
            )
        return raw

    def preprocess(
        self, name: str, raw_images: np.ndarray, *, preprocessed: bool = False
    ) -> np.ndarray:
        """Run the HOST-side ingress for a registered model.

        Returns literals in the model's eval-path input form (dense uint8
        or packed uint32).  With ``preprocessed=True`` the input is only
        validated against that form.  Kept as the reference baseline the
        device-resident ingress is asserted bit-identical against, and
        for callers that want to preprocess once and submit
        ``preprocessed=True`` many times.
        """
        entry = self._servables[name]
        if entry.composite:
            raise ValueError(
                f"composite {name!r} serves raw frames only: its specialists "
                f"have no one literal form"
            )
        path = get_path(entry.path_name)
        if len(raw_images) == 0:
            raise ValueError("empty request")
        if preprocessed:
            lits = np.asarray(raw_images)
            self._validate_preprocessed(lits, path, entry.servable.config.patch)
            return lits
        # The registered ingress knobs apply to BOTH ingresses — a host
        # baseline run with default knobs against a device path with
        # custom ones would silently break the bit-identity contract.
        # (kernel_backend is an IngressSpec-only knob, not a booleanize
        # parameter.)
        host_kw = {
            k: v for k, v in entry.booleanize_kw.items()
            if k in ("threshold", "block_size", "c", "levels")
        }
        return preprocess_for_serving(
            raw_images,
            entry.servable.config.patch,
            method=entry.booleanize_method,
            packed=path.input_form == PACKED,
            **host_kw,
        )

    def dispatch(
        self,
        name: str,
        images: np.ndarray,
        *,
        preprocessed: bool = False,
        ingress: str = "device",
    ) -> InFlightClassify:
        """Submit one request batch and return without waiting on device.

        ``images``: raw uint8 pixels ``[n, Y, X]`` (default; the fused
        device ingress), or — with ``preprocessed`` — literals already in
        the path's input form.  ``ingress='host'`` routes raw pixels
        through the legacy host pipeline instead.  Requests larger than
        ``max_batch`` are dispatched in ``max_batch`` slices.
        """
        if ingress not in ("device", "host"):
            raise ValueError(f"ingress must be 'device' or 'host', got {ingress!r}")
        entry = self._servables[name]
        if self.faults is not None:
            # Chaos seam: may raise InjectedEngineError before any host or
            # device work, standing in for an XLA/runtime dispatch failure.
            self.faults.on_engine_dispatch(name)
        t0 = time.perf_counter()
        if preprocessed:
            arr = self.preprocess(name, images, preprocessed=True)
            form = "literals"
        elif ingress == "host":
            arr = self.preprocess(name, images)
            form = "literals"
        else:
            arr = self.validate_raw(name, images)
            form = "raw"
        t1 = time.perf_counter()
        n = arr.shape[0]
        # The lock pins ONE (servable, version) across every slice of this
        # request: a concurrent swap either lands before (whole request on
        # the new version) or after (whole request on the old, which stays
        # referenced by the submitted executables until .result()).
        with self._lock:
            ver = entry.version.version
            parts = [
                self._submit_bucket(entry, arr[i : i + self.max_batch], form)
                for i in range(0, n, self.max_batch)
            ]
        t2 = time.perf_counter()
        entry.stats.dispatch.record((t2 - t1) * 1e6)
        return InFlightClassify(entry, parts, n, (t0, t1, t2), version=ver)

    def classify(
        self,
        name: str,
        images: np.ndarray,
        *,
        preprocessed: bool = False,
        ingress: str = "device",
    ) -> ClassifyResult:
        """Classify one request batch (blocking ``dispatch().result()``)."""
        return self.dispatch(
            name, images, preprocessed=preprocessed, ingress=ingress
        ).result()
