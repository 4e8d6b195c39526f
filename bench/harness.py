"""What every cell shares: the compile-cache rule, the table of peaks,
the compile meter, the lookup of a cell's files, and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``bench/``, found by
the name ``BENCHMARK.json`` gives it:

  * ``bench/configs/<config>.json``  — the served model's geometry and source
    (the path is the configuration's ``file`` in ``BENCHMARK.json``);
  * ``bench/traffic/<traffic>.json`` — the parameters the one general
    generator (``bench/load.py``) reads;
  * ``bench/metrics/<metric>.py``    — one reader per metric, ``read(record)``;
  * ``bench/configs/<config>.py``    — optional, beside the configuration's
    file with the same stem: the configuration's own code, as hooks that
    replace the defaults in ``bench/family.py`` (:func:`load_family`):

      - ``make_model(jax, cfg, seed)`` -> the model pytree; default
        ``system.make_model_arrays``, ``(ta, weights)``;
      - ``make_frames(rng, n, cfg)`` -> uint8 ``[n, ...]`` raw frames;
        default ``system.make_frames`` at ``image_y`` x ``image_x``;
      - ``build_engine(cfg, traffic, model)`` -> ``(engine, arch)``;
        default ``system.build_engine`` under the file's ``arch``;
      - ``served_work(engine, arch, cfg, model)`` -> the work dict; default
        the nonempty-clause check, then ``work.frame_work``;
      - ``reference(frames, cfg, model, weight_bits=8)`` -> ``(sums,
        preds, ambiguous)`` in NumPy; default ``reference.class_sums``;
      - ``STEP_MODULES`` -> the jitted step names the step metrics count;
        default ``tracefile.STEP_MODULES``.

    Any other public function, class or value the module defines is an
    error at start (a misspelled hook would fall back to the default);
    its helpers start with ``_``.
"""

from __future__ import annotations

import __future__
import gc
import importlib.util
import inspect
import json
import math
import os
import sys
import time
import types
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SPEC_FILE = ROOT / "BENCHMARK.json"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture
#: page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  The
#: counted work is int8 operations (``bench/work.py``), so the int8 peak
#: and the HBM bandwidth are what a roofline reads.
PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip; a device missing from the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


def enable_compile_cache(jax) -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else the fixed ``.jax_cache`` at the root
    of the checkout.  The path is part of each entry's key, so it is never
    made from a temporary name, a pid or the time."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileMeter:
    """XLA compiles and persistent-cache hits/misses, from JAX's
    monitoring events (so a compile inside the window shows)."""

    def __init__(self, jax):
        self.traces = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class GcMeter:
    """Collections of Python's cyclic garbage collector while it is on:
    count per generation and the pauses they took (host clock)."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.pauses = []
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)
            self.count[info["generation"]] += 1

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def summary(self) -> str:
        p = self.pauses
        return (f"gc collections in window gen0={self.count[0]} gen1={self.count[1]} "
                f"gen2={self.count[2]} pause_max_ms={max(p, default=0) * 1e3:.3f} "
                f"pause_total_ms={sum(p) * 1e3:.3f}")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile ``q`` (0-100) of a non-empty sequence."""
    v = np.sort(np.asarray(values))
    return float(v[max(int(np.ceil(q / 100 * len(v))) - 1, 0)])


def load_spec() -> dict:
    with open(SPEC_FILE) as f:
        return json.load(f)


def find_workload(spec: dict, name: str) -> dict:
    for wl in spec["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(
        f"unknown workload {name!r}; known: {[w['name'] for w in spec['workloads']]}"
    )


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"unknown config {name!r}")


def load_config(spec: dict, name: str) -> dict:
    with open(ROOT / config_entry(spec, name)["file"]) as f:
        return json.load(f)


#: What a configuration's own module may define; ``bench/family.py`` has
#: the default of each.
HOOKS = ("make_model", "make_frames", "build_engine", "served_work", "reference",
         "STEP_MODULES")


def _load_module(name: str, path: Path):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _own_names(mod) -> set:
    """The public names ``mod`` defines itself: its functions and classes,
    and every other value but a module or a ``__future__`` feature."""
    own = set()
    for k, v in vars(mod).items():
        if k.startswith("_") or inspect.ismodule(v) or isinstance(v, __future__._Feature):
            continue
        if (inspect.isfunction(v) or inspect.isclass(v)) and v.__module__ != mod.__name__:
            continue                   # imported, not defined here
        own.add(k)
    return own


def load_family(spec: dict, name: str) -> types.SimpleNamespace:
    """The hooks of configuration ``name``: the defaults of
    ``bench/family.py``, each replaced by the one of the same name in the
    module beside the configuration's file, where there is one."""
    import family

    hooks = {h: getattr(family, h) for h in HOOKS}
    path = (ROOT / config_entry(spec, name)["file"]).with_suffix(".py")
    if path.exists():
        mod = _load_module(f"bench_config_{name}", path)
        unknown = sorted(_own_names(mod) - set(HOOKS))
        if unknown:
            raise ValueError(f"{path}: {unknown} are not hooks; a configuration's "
                             f"module defines only {list(HOOKS)} and helpers named _*")
        hooks.update((h, getattr(mod, h)) for h in HOOKS if hasattr(mod, h))
    return types.SimpleNamespace(**hooks)


def load_traffic(name: str) -> dict:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def read_metric(name: str, record: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(record)``; None when the
    reader finds nothing to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    return _load_module(f"bench_metric_{name}", path).read(record)


def device_info(jax, devices) -> dict:
    """Platform, kind, count and the peak memory of the fullest chip used."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def say(*parts) -> None:
    print(*parts, flush=True)


def result_line(*, correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The last line of standard output; ``checks`` comes last."""
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out, allow_nan=False)


def print_checks(checks: dict) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in checks.items():
        ok = c["value"] <= c["limit"]
        print(
            f"check {name} = {c['value']} (limit {c['limit']}) "
            f"{'ok' if ok else 'FAILED'}",
            file=sys.stderr, flush=True,
        )


def finite(x: float) -> bool:
    return x is not None and math.isfinite(x)
