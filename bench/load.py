"""The one general load generator.  A traffic file (``bench/traffic/*.json``)
says which entry the load goes into and how it arrives:

  * ``"entry": "service", "arrivals": "poisson"`` — open loop: requests of
    ``frames_per_request`` frames are due at the times of a Poisson process
    of ``rate_per_s`` (conditioned on its count, so every seed sends the
    same number of requests in the window) and go to
    ``ServingService.submit_nowait`` when due, whatever is still in flight.
    Each request is timed from its due time to its result in hand.
  * ``"entry": "engine", "arrivals": "closed"`` — one closed-loop client:
    calls of ``frames_per_request`` frames to ``ServingEngine`` back to back
    (``dispatch(...).result()``, which is what ``classify`` does) until the
    window has passed.

Frames come from a pool made from the seed; ``check_sample`` answers,
drawn from the seed, are kept for the comparison with the reference as
``(key, frames, class sums, predictions)``, where equal keys mean equal
frames.
Host spans (``bench.*``) mark what the generator was doing, for the
trace's idle gaps.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import time

import numpy as np


def spans(enabled: bool):
    """``span(name)``: a profiler TraceAnnotation when tracing, else nothing."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def plan_open(rng: np.random.Generator, traffic: dict, seconds: float, pool: int):
    """(due times [n], first pool frame of each request [n], sampled [n]):
    ``rate * seconds`` requests at uniform times, the Poisson process
    conditioned on its count."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    due = np.sort(rng.random(n)) * seconds
    start = rng.integers(0, pool - traffic["frames_per_request"] + 1, n)
    sampled = np.zeros(n, bool)
    sampled[rng.choice(n, min(traffic["check_sample"], n), replace=False)] = True
    return due, start, sampled


def plan_closed(rng: np.random.Generator, traffic: dict, batches) -> list:
    """The rows of each pool batch whose answers are kept for the check."""
    per_batch = max(1, traffic["check_sample"] // len(batches))
    return [np.sort(rng.choice(len(b), min(per_batch, len(b)), replace=False))
            for b in batches]


async def open_loop(service, arch, pool, traffic, rng, seconds, m, trace):
    """Poisson arrivals into ``service``; returns the run's record fields."""
    from repro.serve import ServiceOverloaded

    span = spans(trace)
    due, start, sampled = plan_open(rng, traffic, seconds, len(pool))
    n = len(due)
    k = traffic["frames_per_request"]

    latency = np.full(n, np.inf)
    lateness = np.full(n, np.nan)
    status = np.zeros(n, np.int8)          # 0 pending, 1 answered, 2 refused, 3 failed
    sums = np.zeros((n, k, m), np.int64)
    preds = np.zeros((n, k), np.int64)
    loop = asyncio.get_running_loop()
    # Futures are not kept: only a count of those in flight, so the
    # client adds no long-lived objects for the collector to walk.
    state = {"in_flight": 0, "sending": True}
    all_done = asyncio.Event()

    def done(i, t_due, fut):
        t = loop.time()
        state["in_flight"] -= 1
        if not state["in_flight"] and not state["sending"]:
            all_done.set()
        if fut.cancelled() or fut.exception() is not None:
            status[i] = 3
            return
        status[i] = 1
        latency[i] = t - t_due
        if sampled[i]:
            r = fut.result()
            sums[i] = r.class_sums
            preds[i] = r.predictions

    t0 = loop.time() + 0.001
    i = 0
    with span("bench.window"):
        while i < n:
            now = loop.time()
            if t0 + due[i] > now:
                with span("bench.gen.sleep"):
                    await asyncio.sleep(t0 + due[i] - now)
                continue
            with span("bench.gen.submit"):
                while i < n and t0 + due[i] <= now:
                    lateness[i] = loop.time() - (t0 + due[i])
                    s = start[i]
                    try:
                        fut = service.submit_nowait(arch, pool[s:s + k])
                    except ServiceOverloaded:
                        status[i] = 2
                    else:
                        state["in_flight"] += 1
                        fut.add_done_callback(functools.partial(done, i, t0 + due[i]))
                    i += 1
            await asyncio.sleep(0)
    window_s = loop.time() - t0
    state["sending"] = False
    if state["in_flight"]:
        with span("bench.await"):
            try:
                await asyncio.wait_for(all_done.wait(), traffic["grace_s"])
            except asyncio.TimeoutError:
                pass
    status[status == 0] = 3                # never answered within the grace
    return {
        "seconds": window_s,
        "due": n,
        "latency_s": latency,
        "lateness_s": lateness[~np.isnan(lateness)],
        "worst_late_at_s": [round(float(due[j]), 3)
                            for j in np.argsort(np.nan_to_num(lateness))[-3:]],
        "refused": int((status == 2).sum()),
        "unanswered": int((status == 3).sum()),
        "answered": int((status == 1).sum()),
        "checked": [(int(start[i]), pool[start[i]:start[i] + k], sums[i], preds[i])
                    for i in np.flatnonzero(sampled & (status == 1))],
    }


def closed_loop(engine, arch, batches, traffic, rng, seconds, trace):
    """One client calling the engine back to back for ``seconds``."""
    span = spans(trace)
    rows = plan_closed(rng, traffic, batches)
    outs, calls, frames, failed = [], 0, 0, 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with span("bench.window"):
        while True:
            b = calls % len(batches)
            calls += 1
            try:
                with span("bench.dispatch"):
                    handle = engine.dispatch(arch, batches[b])
                with span("bench.await"):
                    res = handle.result()
            except Exception as e:         # counted, and shown once
                if not failed:
                    print(f"call failed: {e!r}", flush=True)
                failed += 1
            else:
                frames += len(res.predictions)
                outs.append((b, res.class_sums[rows[b]], res.predictions[rows[b]]))
            if time.perf_counter() >= deadline:
                break
    window_s = time.perf_counter() - t0
    return {
        "seconds": window_s,
        "due": calls,
        "frames_done": frames,
        "refused": 0,
        "unanswered": failed,
        "answered": calls - failed,
        "checked": [(b, batches[b][rows[b]], s, p) for b, s, p in outs],
    }
