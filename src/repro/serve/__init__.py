"""Serving subsystem: frozen model artifacts + batched engine + async service.

``servable``  — :class:`ServableModel`, the software image of the ASIC's
                45k-bit register file (frozen include bits, packed include
                words, nonempty mask, int8-clamped weights), prepared
                exactly once per model; :class:`CompositeServable`, one
                frozen image per specialist of a TM Composite.
``paths``     — registry of functionally identical evaluation paths
                (dense / bitpacked / matmul / kernel / fused), each
                owning its full raw->sums graph via an ``ingress_fn``;
                every inference consumer dispatches through it.
``engine``    — :class:`ServingEngine`, batched multi-dataset serving with
                power-of-two batch bucketing, the fused device-resident
                raw classify step, async dispatch handles and
                per-stage latency histograms (the synchronous
                library layer).
``scheduler`` — :class:`MicrobatchScheduler`, the latency-aware
                microbatching policy (per-model queues, round-robin,
                deadline coalescing, high-water admission).
``service``   — :class:`ServingService`, the asyncio request-queue front
                end over the engine: backpressure, microbatching,
                multi-model fairness, graceful drain, p50/p99 stats.
``mesh``      — :class:`ServeMesh`, multi-device placement: servables
                replicated (or clause-sharded) across a ("data","model")
                mesh, request buckets sharded over "data" inside the
                engine's jitted steps — bit-identical to single-device.
``autotune``  — :class:`TunedPlan` + the per-bucket eval-path autotuner:
                measures every admissible (path, params) candidate per
                (form, bucket) and pins deterministic winners on the
                servable (hashable, JSON-serializable with checkpoints).
``faults``    — :class:`FaultPlan` deterministic fault injection,
                :class:`DegradationPolicy` circuit-breaker knobs,
                :class:`ServiceHealth` state, the structured fault errors
                every request future resolves with, and the chaos-soak
                driver (ARCHITECTURE.md §Faults).
``telemetry`` — ``span`` (profiler host spans) and :class:`Histogram`
                (stage durations) of the serving path
                (ARCHITECTURE.md §Telemetry).
"""

from repro.serve.autotune import AutotuneReport, TunedPlan, autotune_servable
from repro.serve.engine import (
    ClassifyResult,
    InFlightClassify,
    ServeStats,
    ServingEngine,
    classify_composite_step,
    classify_raw_step,
    classify_step,
)
from repro.serve.faults import (
    DegradationPolicy,
    DeviceLost,
    FaultError,
    FaultPlan,
    InjectedEngineError,
    PoisonedPayload,
    ServiceExpired,
    ServiceHealth,
    StepCompileError,
    WorkerCrashed,
    chaos_soak,
)
from repro.serve.loadgen import LoadReport, poisson_open_loop
from repro.serve.mesh import ServeMesh, classify_step_meshed, make_serve_mesh
from repro.serve.paths import (
    DENSE,
    PACKED,
    RAW,
    EvalPath,
    available_paths,
    degraded_fallback,
    get_path,
    register_path,
    resolve_path,
    run_path,
    run_path_raw,
)
from repro.serve.scheduler import (
    MicrobatchScheduler,
    PendingRequest,
    QueueFull,
    SchedulerConfig,
)
from repro.serve.servable import (
    ClauseSparsity,
    CompositeServable,
    ServableModel,
    ServableVersion,
    active_pad,
    analyze_sparsity,
    freeze,
    freeze_composite,
    servable_digest,
)
from repro.serve.service import (
    ServiceConfig,
    ServiceOverloaded,
    ServiceResult,
    ServiceStats,
    ServiceStopped,
    ServingService,
)

__all__ = [
    "DENSE",
    "PACKED",
    "RAW",
    "AutotuneReport",
    "ClassifyResult",
    "ClauseSparsity",
    "CompositeServable",
    "DegradationPolicy",
    "DeviceLost",
    "EvalPath",
    "FaultError",
    "FaultPlan",
    "InFlightClassify",
    "InjectedEngineError",
    "LoadReport",
    "MicrobatchScheduler",
    "PendingRequest",
    "PoisonedPayload",
    "QueueFull",
    "SchedulerConfig",
    "ServableModel",
    "ServableVersion",
    "ServeMesh",
    "ServeStats",
    "ServiceConfig",
    "ServiceExpired",
    "ServiceHealth",
    "ServiceOverloaded",
    "ServiceResult",
    "ServiceStats",
    "ServiceStopped",
    "ServingEngine",
    "ServingService",
    "StepCompileError",
    "TunedPlan",
    "WorkerCrashed",
    "active_pad",
    "analyze_sparsity",
    "autotune_servable",
    "available_paths",
    "chaos_soak",
    "classify_composite_step",
    "classify_raw_step",
    "classify_step",
    "classify_step_meshed",
    "degraded_fallback",
    "freeze",
    "freeze_composite",
    "make_serve_mesh",
    "get_path",
    "poisson_open_loop",
    "register_path",
    "resolve_path",
    "run_path",
    "run_path_raw",
    "servable_digest",
]
