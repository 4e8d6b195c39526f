"""95th percentile (nearest rank) of the latencies ``p50_latency_us`` reads.

The 95th and not the 99th: on a TPU v5e about 0.8% of the sensors cell's
requests are held up by host stalls of ~118 ms (PERF.md), so the 99th
percentile falls on the edge of that group and reads 8 ms in one run and
50 ms in the next.  ``gen_lag_p99_us.sensors`` shows the stalls."""

from harness import percentile


def read(record):
    lat = record.get("latency_s")
    if lat is None or not len(lat):
        return None
    return percentile(lat, 95) * 1e6
