"""The program's stage histograms over the measured window.

``ServeStats`` (``dispatch``, ``wait``, ``fetch``) and ``ServiceStats``
(``queue``, ``slot``, ``dispatch``, ``complete``, ``resolve``,
``latency``) hold ``repro.serve.telemetry.Histogram`` counters.  A
snapshot before the window and one after subtract into the window's
counts, kept as plain lists: ``{stage: {"edges": [...], "counts":
[...]}}``, edges in us.  A program without the histograms snapshots as
``{}``, and every reading of it is None.
"""

from __future__ import annotations

import math

ENGINE_STAGES = ("dispatch", "wait", "fetch")
SERVICE_STAGES = ("queue", "slot", "dispatch", "complete", "resolve", "latency")


def snapshot(stats, names) -> dict:
    """``{stage: counts}`` of the histograms ``stats`` has among ``names``."""
    out = {}
    for name in names:
        counts = getattr(getattr(stats, name, None), "counts", None)
        if counts is not None:
            out[name] = list(counts)
    return out


def window(before: dict, after: dict) -> dict:
    """The counts recorded between two snapshots, with the bucket edges."""
    if not after:
        return {}
    from repro.serve.telemetry import EDGES_US

    return {
        name: {"edges": list(EDGES_US),
               "counts": [b - a for a, b in zip(before.get(name, [0] * len(c)), c)]}
        for name, c in after.items()
    }


def quantile(stages: dict | None, name: str, q: float) -> float | None:
    """Nearest-rank quantile ``q`` (0-1) of one stage, as the upper edge of
    its bucket in us; None when the stage is absent or empty."""
    h = (stages or {}).get(name)
    if not h:
        return None
    total = sum(h["counts"])
    if not total:
        return None
    rank = max(math.ceil(q * total), 1)
    seen = 0
    for c, edge in zip(h["counts"], h["edges"]):
        seen += c
        if seen >= rank:
            return edge
    return None


def summary(stages: dict) -> dict:
    """Count and p50/p95/p99 (us) of each stage, for a report line."""
    return {
        name: {"count": sum(h["counts"]),
               **{f"p{int(q * 100)}_us": quantile(stages, name, q)
                  for q in (0.5, 0.95, 0.99)}}
        for name, h in stages.items()
    }
