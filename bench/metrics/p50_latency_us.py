"""Median latency of every request due in the window, from its due time
to its result in hand; a refused or failed request counts as +inf."""

from harness import percentile


def read(record):
    lat = record.get("latency_s")
    if lat is None or not len(lat):
        return None
    return percentile(lat, 50) * 1e6
