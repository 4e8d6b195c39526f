"""Spans and stage histograms of the serving path (ARCHITECTURE.md §Telemetry).

Two things, and no configuration:

  * :data:`span` — ``span(name, **args)`` is ``jax.profiler.TraceAnnotation``:
    a host span written into the profiler's host plane, on the same clock
    as the device planes.  While the profiler is not tracing it costs
    the object alone.  The serving path opens spans per microbatch and per
    chunk, never per request.
  * :class:`Histogram` — durations in microseconds over fixed log-linear
    buckets, O(1) to record in pure Python.  Each histogram has ONE
    writer thread (the thread that owns the stage it times); readers take
    a :meth:`Histogram.copy`, and two copies subtract into a window.
"""

from __future__ import annotations

import math
from math import frexp
from typing import List

import jax

__all__ = ["Histogram", "span"]

#: ``span(name, **args)``: a host span on the profiler's clock.
span = jax.profiler.TraceAnnotation

PER_OCTAVE = 8
OCTAVES = 27
#: Finite buckets: 8 per octave from 1 us up to 2**27 us (~134 s).
N_FINITE = PER_OCTAVE * OCTAVES
TOP_US = float(1 << OCTAVES)


def _upper_edges() -> List[float]:
    return [
        (1 << (i // PER_OCTAVE)) * (1.0 + (i % PER_OCTAVE + 1) / PER_OCTAVE)
        for i in range(N_FINITE)
    ] + [math.inf]


#: Upper edge of each bucket, in us; the last bucket (overflow) is open.
#: Bucket 0 also holds everything below 1 us.
EDGES_US = tuple(_upper_edges())


class Histogram:
    """Counts of durations (us) in the buckets of :data:`EDGES_US`.

    A bucket spans 1/8 of its octave, so its width is 6-12.5% of its
    lower edge; a quantile is read as the upper edge of the bucket that
    holds it.
    """

    __slots__ = ("counts",)

    def __init__(self, counts=None):
        self.counts = [0] * len(EDGES_US) if counts is None else list(counts)

    def record(self, us: float, n: int = 1) -> None:
        """Count ``n`` durations of ``us`` microseconds."""
        if us < 1.0:
            i = 0
        elif us < TOP_US:
            # us = m * 2**e with 0.5 <= m < 1: octave e - 1, and the
            # eighth of it is int(16 * m) - 8 (literals: PER_OCTAVE = 8).
            m, e = frexp(us)
            i = 8 * e + int(16 * m) - 16
        else:                                # overflow, inf and nan
            i = N_FINITE
        self.counts[i] += n

    @property
    def count(self) -> int:
        return sum(self.counts)

    def quantile(self, q: float) -> float:
        """Nearest-rank quantile ``q`` in [0, 1], as the upper edge (us) of
        the bucket holding it; 0.0 when nothing was recorded."""
        total = self.count
        if not total:
            return 0.0
        rank = max(math.ceil(q * total), 1)
        seen = 0
        for c, edge in zip(self.counts, EDGES_US):
            seen += c
            if seen >= rank:
                return edge
        return EDGES_US[-1]

    def copy(self) -> "Histogram":
        return Histogram(self.counts)

    def __sub__(self, other: "Histogram") -> "Histogram":
        """The counts recorded between snapshot ``other`` and this one."""
        return Histogram([a - b for a, b in zip(self.counts, other.counts)])

    def summary(self) -> dict:
        """Count, p50 and p99 (us): the plain-data form for reports."""
        return {
            "count": self.count,
            "p50_us": self.quantile(0.50),
            "p99_us": self.quantile(0.99),
        }

    def __repr__(self) -> str:
        s = self.summary()
        return f"Histogram(count={s['count']}, p50_us={s['p50_us']}, p99_us={s['p99_us']})"
