"""The program's stage histograms windowed over a run, the device's idle
gaps labelled by program spans, and the probe that prints both around a
cell run (on the CPU at a tiny size)."""

import collections
import json
import time
import types

import jax
import pytest

import harness
import spans
import stages
from probe import probe_cell
from repro.serve.telemetry import EDGES_US, Histogram

Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")
Data = collections.namedtuple("Data", "planes")


def _stats(**values):
    out = types.SimpleNamespace()
    for name, vs in values.items():
        h = Histogram()
        for v in vs:
            h.record(v)
        setattr(out, name, h)
    return out


def test_window_is_the_difference_of_two_snapshots():
    before = stages.snapshot(_stats(queue=[10.0] * 5, latency=[900.0]), stages.SERVICE_STAGES)
    after = stages.snapshot(_stats(queue=[10.0] * 5 + [300.0] * 3, latency=[900.0] * 2),
                            stages.SERVICE_STAGES)
    assert set(after) == {"queue", "latency"}
    win = stages.window(before, after)
    assert win["queue"]["edges"] == list(EDGES_US)
    assert sum(win["queue"]["counts"]) == 3 and sum(win["latency"]["counts"]) == 1
    assert 300.0 < stages.quantile(win, "queue", 0.5) <= 300.0 * 1.125
    assert 900.0 < stages.quantile(win, "latency", 0.99) <= 900.0 * 1.125
    s = stages.summary(win)
    assert s["queue"]["count"] == 3 and s["queue"]["p95_us"] == stages.quantile(win, "queue", 0.95)


def test_program_without_histograms_reads_nothing():
    old = types.SimpleNamespace(images=10, batches=2)      # no histograms
    snap = stages.snapshot(old, stages.ENGINE_STAGES)
    assert snap == {} and stages.window(snap, snap) == {}
    assert stages.quantile({}, "dispatch", 0.5) is None
    assert stages.quantile(None, "dispatch", 0.5) is None
    empty = stages.window({}, stages.snapshot(_stats(fetch=[]), stages.ENGINE_STAGES))
    assert stages.quantile(empty, "fetch", 0.5) is None


def _trace():
    host = Plane("/host:CPU", [
        Line("python", [
            Ev("bench.window", 1000, 9000),               # window [1000, 10000)
            Ev("bench.await", 1000, 9000),                # never a label
        ]),
        Line("serve-dispatch", [
            Ev("serve.dispatch", 1400, 700),              # [1400, 2100)
            Ev("serve.engine.put", 1500, 500),            # inner, same overlap
        ]),
        Line("serve-complete", [
            Ev("serve.complete", 4000, 5000),             # [4000, 9000)
            Ev("serve.engine.fetch", 7000, 1500),         # [7000, 8500)
        ]),
    ])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("fusion.1", 500, 1000),                        # [1000, 1500) in window
        Ev("fusion.2", 2000, 2500),                       # [2000, 4500)
        Ev("fusion.3", 8000, 1000),                       # [8000, 9000)
    ])])
    return Data([host, dev])


def test_idle_gaps_labelled_by_program_span():
    t = spans.idle_by_span(_trace())
    # gaps [1500,2000) dispatch and put cover 500 each: put is innermost;
    # [4500,8000) complete covers 3500; [9000,10000) no serve span.
    assert t["window_s"] == pytest.approx(9000e-9)
    assert t["idle_s"] == pytest.approx(
        {"serve.complete": 3500e-9, "none": 1000e-9, "serve.engine.put": 500e-9})
    assert [g[0] for g in t["longest"]] == ["serve.complete", "none", "serve.engine.put"]
    assert sum(t["idle_s"].values()) == pytest.approx(5000e-9)


def test_idle_by_span_without_program_spans_or_window():
    d = _trace()
    host = Plane("/host:CPU", [Line("python", [Ev("bench.window", 1000, 9000)])])
    t = spans.idle_by_span(Data([host, d.planes[1]]))
    assert list(t["idle_s"]) == ["none"]
    assert spans.idle_by_span(Data(d.planes[1:])) is None
    assert spans.idle_by_span(Data(d.planes[:1])) is None


@pytest.mark.parametrize("name", ["mnist-sensors", "mnist-bulk", "mnist-bulk-mesh4"])
def test_probe_prints_stages_and_idle_by_span(name, capsys):
    wl = harness.find_workload(harness.load_spec(), name)
    traffic = harness.load_traffic(wl["traffic"])
    tiny = {"engine": dict(frames_per_request=512, pool_requests=2, check_sample=256),
            "service": dict(rate_per_s=150, pool_frames=512, check_sample=96, grace_s=30)}
    traffic.update(tiny[traffic["entry"]])
    probe_cell(name, 2**31 + 5, 1.0, True, jax.devices()[:wl["chips"]], time.monotonic(),
               traffic=traffic, peaks=harness.peaks_for("TPU v5 lite"))
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["correct"] is True
    got = json.loads(next(x for x in out if x.startswith("stages "))[len("stages "):])
    assert got["engine"]["dispatch"]["count"] > 0 and got["engine"]["fetch"]["p50_us"] > 0
    if name == "mnist-sensors":
        assert got["service"]["queue"]["count"] == got["service"]["latency"]["count"] > 0
    else:
        assert "service" not in got
    # The CPU trace has no /device:TPU plane, so there is nothing to label.
    assert "idle by span null" in out
