"""99th percentile (nearest rank) of how late the open-loop generator sent
each request after its due time, on the benchmark's clock."""

from harness import percentile


def read(record):
    lag = record.get("lateness_s")
    if lag is None or not len(lag):
        return None
    return percentile(lag, 99) * 1e6
