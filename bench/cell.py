"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.  ``bench/run.py`` calls
:func:`run_cell` after it has found the chips the cell asks for; the
tests call it directly on the CPU."""

from __future__ import annotations

import asyncio
import gc
import shutil
import time

import numpy as np

import harness
import load
import tracefile
from harness import say


class _Window:
    """Compile count and profiler around the measured window."""

    def __init__(self, jax, meter, trace_dir, t_start):
        self.jax, self.meter, self.trace_dir = jax, meter, trace_dir
        self.marks = [("start", t_start)]
        self.mark("imports and chips")

    def mark(self, phase):
        """End of a set-up phase, for the set-up line."""
        self.marks.append((phase, time.monotonic()))

    def open(self):
        self.mark("frames and warm-up")
        # Set-up's cyclic garbage is collected here, in set-up, and not by
        # a full collection that would otherwise fall inside the window.
        gc.collect()
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # Python call tracing would slow the host
            self.jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self.counts = (self.meter.traces, self.meter.compiles, self.meter.hits)
        self.gc = harness.GcMeter().__enter__()
        self.mark("collect" + (" and trace start" if self.trace_dir else ""))
        self.setup_s = self.marks[-1][1] - self.marks[0][1]

    def close(self):
        self.gc.__exit__()
        now = (self.meter.traces, self.meter.compiles, self.meter.hits)
        self.traces, self.compiles, self.loads = (b - a for a, b in zip(self.counts, now))
        if self.trace_dir is not None:
            self.jax.profiler.stop_trace()


def _run_service(engine, arch, cfg, family, traffic, rng, seconds, trace, window):
    from repro.serve import ServiceConfig, ServingService

    pool = family.make_frames(rng, traffic["pool_frames"], cfg)
    engine.warmup(arch, forms=("raw",))       # every bucket a microbatch can hit

    async def serve():
        service = ServingService(engine, ServiceConfig())
        await service.start()
        try:
            for j in range(8):
                await service.submit(arch, pool[j:j + 1])
            await asyncio.gather(
                *(service.submit_nowait(arch, pool[j:j + 1]) for j in range(512)))
            before = service.stats(arch)
            window.open()
            rec = await load.open_loop(service, arch, pool, traffic, rng, seconds,
                                       cfg["n_classes"], trace)
            window.close()
            after = service.stats(arch)
        finally:
            await service.stop(drain=True)
        rec["counters"] = {"images": after.images - before.images,
                           "batches": after.batches - before.batches}
        return rec

    return asyncio.run(serve())


def _run_engine(engine, arch, cfg, family, traffic, rng, seconds, trace, window):
    k = traffic["frames_per_request"]
    batches = [family.make_frames(rng, k, cfg) for _ in range(traffic["pool_requests"])]
    chunks = {min(engine.max_batch, k - i) for i in range(0, k, engine.max_batch)}
    engine.warmup(arch, buckets=sorted(chunks), forms=("raw",))
    engine.classify(arch, batches[0])
    window.open()
    rec = load.closed_loop(engine, arch, batches, traffic, rng, seconds, trace)
    window.close()
    return rec


def compare(checked, cfg, family, model, weight_bits=8):
    """Rows whose class sums (of any trailing shape) or prediction differ
    from those of the family's reference on ``model`` (NumPy arrays),
    among the answers kept; frames the reference calls ambiguous are left
    out and counted."""
    keys = {}
    for key, frames, _, _ in checked:
        keys.setdefault(key, frames)
    if not keys:
        return {"rows": 0, "rows_wrong": 0, "preds_wrong": 0, "ambiguous": 0}
    order = list(keys)
    sizes = [len(keys[k]) for k in order]
    sums, preds, amb = family.reference(
        np.concatenate([keys[k] for k in order]), cfg, model, weight_bits=weight_bits)
    offs = dict(zip(order, np.cumsum([0] + sizes[:-1])))
    rows = rows_wrong = preds_wrong = ambiguous = 0
    for key, frames, got_sums, got_preds in checked:
        o = offs[key]
        sl = slice(o, o + len(frames))
        ok = ~amb[sl]
        rows += int(ok.sum())
        ambiguous += int((~ok).sum())
        differ = (got_sums != sums[sl]).reshape(len(frames), -1).any(axis=1)
        rows_wrong += int((differ & ok).sum())
        preds_wrong += int(((got_preds != preds[sl]) & ok).sum())
    return {"rows": rows, "rows_wrong": rows_wrong, "preds_wrong": preds_wrong,
            "ambiguous": ambiguous}


def run_cell(name, seed, seconds, trace, devices, t_start, *, spec=None,
             traffic=None, peaks=None):
    """Run the cell ``name`` of ``spec`` (default ``BENCHMARK.json``) once on
    ``devices`` and print its lines; the last line of standard output is
    the result."""
    import jax

    spec = spec or harness.load_spec()
    wl = harness.find_workload(spec, name)
    cfg = harness.load_config(spec, wl["config"])
    family = harness.load_family(spec, wl["config"])
    traffic = traffic or harness.load_traffic(wl["traffic"])
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    peaks = peaks or harness.peaks_for(dev.device_kind)
    say(f"compile cache: {harness.enable_compile_cache(jax)}")
    meter = harness.CompileMeter(jax)
    rng = np.random.default_rng(seed)

    trace_dir = harness.ROOT / ".bench_trace" / name if trace else None
    window = _Window(jax, meter, trace_dir, t_start)
    model = family.make_model(jax, cfg, seed)
    engine, arch = family.build_engine(cfg, traffic, model)
    window.mark("model and engine")
    model = jax.tree.map(np.asarray, model)
    work_ = family.served_work(engine, arch, cfg, model)

    kind = traffic["entry"]
    runner = {"service": _run_service, "engine": _run_engine}[kind]
    rec = runner(engine, arch, cfg, family, traffic, rng, seconds, trace, window)
    rec.update(kind=kind, work=work_, peaks=peaks, chips=len(devices),
               setup_s=window.setup_s, step_modules=tuple(family.STEP_MODULES),
               frames_per_step_event=engine.max_batch // engine.data_shards)
    device = harness.device_info(jax, devices)

    say("set-up s: " + ", ".join(f"{p}={t - t0:.3f}" for (_, t0), (p, t)
                                  in zip(window.marks, window.marks[1:])))
    say(f"requests due={rec['due']} sent={rec['due']} completed={rec['answered']} "
        f"refused={rec['refused']} failed={rec['unanswered']} "
        f"window_s={rec['seconds']:.6f}")
    if "latency_s" in rec:
        lat = rec["latency_s"] * 1e6
        say("latency us (due to answer): " + " ".join(
            f"p{q}={harness.percentile(lat, q):.1f}" for q in (50, 90, 95, 99, 99.9))
            + f" max={lat.max():.1f}")
        lag = rec["lateness_s"] * 1e6
        say(f"generator lateness us: p50={harness.percentile(lag, 50):.1f} "
            f"p99={harness.percentile(lag, 99):.1f} max={lag.max():.1f} "
            f"(latest sends due at s: {rec['worst_late_at_s']})")
    say(f"in window: jit traces={window.traces} xla compiles={window.compiles} "
        f"cache loads={window.loads} (0 expected); process total "
        f"compiles={meter.compiles} compile_s={meter.compile_s:.3f} "
        f"cache hits={meter.hits} misses={meter.misses}")
    say(window.gc.summary())
    say(f"memory peak_bytes_in_use={device['memory_peak_bytes']}")

    breakdown = None
    if trace:
        t0 = time.monotonic()
        rec["trace"] = tracefile.reduce_trace(str(trace_dir), len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        t = rec["trace"]
        say(f"trace reduced in {time.monotonic() - t0:.3f} s: "
            + ("no device plane" if t is None else
               f"window_s={t['window_s']} busy_s={t['busy_s']} modules={t['modules']}"))
        if t is not None:
            device.update(busy_s=t["busy_s"], window_s=t["window_s"])
            breakdown = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}

    t0 = time.monotonic()
    cmp = compare(rec["checked"], cfg, family, model)
    say(f"reference: {cmp['rows']} rows compared ({cmp['ambiguous']} ambiguous left "
        f"out) in {time.monotonic() - t0:.3f} s")
    checks = {
        "rows_wrong": {"value": cmp["rows_wrong"], "limit": 0},
        "preds_wrong": {"value": cmp["preds_wrong"], "limit": 0},
        "unanswered": {"value": rec["unanswered"], "limit": 0},
        "no_rows_compared": {"value": int(cmp["rows"] == 0), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in harness.cell_metrics(spec, name, trace):
        v = harness.read_metric(m["name"], rec)
        if harness.finite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            say(f"metric {m['name']}: nothing to read ({v})")
    harness.print_checks(checks)
    print(harness.result_line(
        correct=correct, attempted=rec["due"], failed=rec["refused"] + rec["unanswered"],
        metrics=metrics, device=device, checks=checks, breakdown=breakdown),
        flush=True)
    return correct
