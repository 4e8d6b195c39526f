"""Device idle time by program span.

Each gap between operations on the first device, inside the benchmark's
``bench.window`` span, is labelled with the program's ``serve.*`` host
span that covers most of it, on any host thread: the innermost (the
shortest) on a tie, ``none`` where no such span overlaps it.  The
reduction gives the idle seconds per label and the longest gaps so
labelled; a trace without ``serve.*`` spans labels every gap ``none``.
"""

from __future__ import annotations

import bisect
import collections

from tracefile import DEVICE_PREFIX, OPS_LINE, TOP, WINDOW_SPAN, _union, find_xplane

PREFIX = "serve."


def idle_by_span(data) -> dict | None:
    """``data``: a ``jax.profiler.ProfileData``.  None when the trace holds
    no device plane or no window span."""
    window, spans = None, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    devices = sorted((p for p in data.planes if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    if window is None or not devices:
        return None
    lo, hi = window
    ops = []
    for line in devices[0].lines:
        if line.name == OPS_LINE:
            ops = [(max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi))
                   for ev in line.events]
    edges = [lo] + [x for iv in _union([o for o in ops if o[1] > o[0]]) for x in iv] + [hi]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]

    # best[g] = (overlap, -span length, label) of gap g; each span visits
    # only the gaps it overlaps (gaps are sorted and disjoint).
    starts = [s for s, _ in gaps]
    best = [(0, 0, "none")] * len(gaps)
    for s, e, name in spans:
        g = max(bisect.bisect_right(starts, s) - 1, 0)
        while g < len(gaps) and gaps[g][0] < e:
            ov = min(e, gaps[g][1]) - max(s, gaps[g][0])
            if ov > 0 and (ov, s - e) > best[g][:2]:
                best[g] = (ov, s - e, name)
            g += 1

    idle = collections.Counter()
    labelled = []
    for (s, e), (_, _, name) in zip(gaps, best):
        idle[name] += (e - s) * 1e-9
        labelled.append([name, (e - s) * 1e-9])
    labelled.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": dict(idle.most_common()),
        "longest": labelled[:TOP],
    }


def reduce_trace(trace_dir: str) -> dict | None:
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    return None if path is None else idle_by_span(ProfileData.from_file(path))
