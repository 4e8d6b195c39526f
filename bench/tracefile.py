"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Device planes are ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per operation run and ``XLA Modules`` one per program
run (the jitted step).  The window is the benchmark's own host span
``bench.window``.  Within it:

  * busy: the union of the operation intervals, per device;
  * modules: count and summed device time of each program, over devices;
  * device_ops: the operations that took most time, over devices;
  * idle_gaps: the longest gaps between operations on the first device,
    each labelled with the ``bench.*`` host span that overlaps it most
    (``none`` where no benchmark span does).
"""

from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
TOP = 10
#: The classify step programs: the single-device raw step and the meshed step.
STEP_MODULES = ("jit__classify_raw_step", "jit__classify_meshed")


def op_label(hlo: str) -> str:
    """``%fusion.3 = u8[256,361]{1,0:T(8,128)} fusion(...)`` -> ``%fusion.3 =
    u8[256,361] fusion``: the instruction, its shape and its opcode."""
    return re.sub(r"\{[^{}]*\}", "", hlo).split("(", 1)[0].strip()


def find_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_xspace(data, n_devices: int) -> dict | None:
    """``data``: a ``jax.profiler.ProfileData``.  None when the trace holds
    no device plane or no window span."""
    host_spans = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host_spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    devices = sorted(
        (p for p in data.planes if p.name.startswith(DEVICE_PREFIX)),
        key=lambda p: int(p.name[len(DEVICE_PREFIX):]),
    )[:n_devices]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    labels = [(name, s, e) for name, s, e in host_spans if name != WINDOW_SPAN]

    busy_ns, ops, modules, gaps = [], collections.Counter(), {}, []
    for k, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        intervals = []
        for ev in lines[OPS_LINE].events if OPS_LINE in lines else ():
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if e > s:
                intervals.append((s, e))
                ops[op_label(ev.name)] += (e - s) * 1e-9
        merged = _union(intervals)
        busy_ns.append(sum(e - s for s, e in merged))
        for ev in lines[MODULES_LINE].events if MODULES_LINE in lines else ():
            if lo <= ev.start_ns < hi:
                n, t = modules.get(ev.name, (0, 0.0))
                modules[ev.name] = (n + 1, t + ev.duration_ns * 1e-9)
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for s, e in gaps[:TOP]:
        best, best_ns = "none", 0
        for name, hs, he in labels:
            ov = min(e, he) - max(s, hs)
            if ov > best_ns:
                best, best_ns = name, ov
        idle.append([best, (e - s) * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "devices": len(devices),
        "modules": modules,
        "device_ops": [[n, t] for n, t in ops.most_common(TOP)],
        "idle_gaps": idle,
    }


def reduce_trace(trace_dir: str, n_devices: int) -> dict | None:
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_xspace(ProfileData.from_file(path), n_devices)


def idle_share_pct(record: dict) -> float | None:
    t = record.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def step_events(record: dict, names) -> tuple[int, float]:
    """(count, summed device seconds) of the modules whose name starts
    with one of ``names``."""
    t = record.get("trace") or {}
    n, s = 0, 0.0
    for name, (count, secs) in (t.get("modules") or {}).items():
        if name.startswith(tuple(names)):
            n += count
            s += secs
    return n, s
