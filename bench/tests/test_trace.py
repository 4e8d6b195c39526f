"""The trace reduction: busy union, step programs by module name, idle gaps
labelled by the benchmark's host spans; on a hand-built trace and on a
small trace recorded on a TPU v5e."""

import collections
import gzip
from pathlib import Path

import pytest

import tracefile

Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")
Data = collections.namedtuple("Data", "planes")

RECORDED = Path(__file__).parent / "data" / "v5e_bulk_probe.xplane.pb.gz"


def _data():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 1000, 9000),          # window [1000, 10000)
        Ev("bench.dispatch", 1000, 1500),
        Ev("bench.await", 6000, 3900),
    ])])
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            Ev("fusion.1", 500, 1000),            # clipped to [1000, 1500)
            Ev("convolution.2", 2000, 2000),      # [2000, 4000)
            Ev("fusion.3", 3500, 1000),           # overlaps: union to 4500
            Ev("fusion.1", 8000, 1000),           # [8000, 9000)
        ]),
        Line("XLA Modules", [
            Ev("jit__classify_raw_step(7)", 2000, 2500),
            Ev("jit__classify_raw_step(7)", 8000, 1000),
            Ev("jit_other", 20000, 10),           # outside the window
        ]),
    ])
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [Ev("fusion.9", 1000, 3000)])])
    return Data([host, dev0, dev1])


def test_busy_union_and_window():
    t = tracefile.reduce_xspace(_data(), 1)
    assert t["window_s"] == pytest.approx(9000e-9)
    # [1000,1500) + [2000,4500) + [8000,9000) = 500 + 2500 + 1000 ns
    assert t["busy_s"] == pytest.approx(4000e-9)
    assert t["devices"] == 1


def test_busy_is_averaged_over_devices():
    t = tracefile.reduce_xspace(_data(), 2)
    assert t["busy_s"] == pytest.approx((4000e-9 + 3000e-9) / 2)


def test_step_modules_counted_by_name():
    rec = {"trace": tracefile.reduce_xspace(_data(), 1)}
    n, s = tracefile.step_events(rec, tracefile.STEP_MODULES)
    assert n == 2 and s == pytest.approx(3500e-9)


def test_idle_gaps_labelled_by_host_span():
    t = tracefile.reduce_xspace(_data(), 1)
    # gaps: [1500,2000) dispatch, [4500,8000) mostly await, [9000,10000) await
    gaps = {round(s * 1e9): label for label, s in t["idle_gaps"]}
    assert gaps == {3500: "bench.await", 1000: "bench.await", 500: "bench.dispatch"}
    assert [round(s * 1e9) for _, s in t["idle_gaps"]] == [3500, 1000, 500]


def test_device_ops_ranked_by_time():
    t = tracefile.reduce_xspace(_data(), 1)
    assert t["device_ops"][0][0] == "convolution.2"
    assert dict(t["device_ops"])["fusion.1"] == pytest.approx(1500e-9)


def test_no_window_or_no_device_reads_nothing():
    d = _data()
    assert tracefile.reduce_xspace(Data(d.planes[1:]), 1) is None
    assert tracefile.reduce_xspace(Data(d.planes[:1]), 1) is None
    assert tracefile.idle_share_pct({"trace": None}) is None


def test_recorded_v5e_trace(tmp_path):
    """Three 512-frame classify calls on the matmul path, recorded on a
    TPU v5 lite with the benchmark's spans around them."""
    from jax.profiler import ProfileData

    raw = gzip.decompress(RECORDED.read_bytes())
    t = tracefile.reduce_xspace(ProfileData.from_serialized_xspace(raw), 1)
    assert t is not None
    assert 0 < t["busy_s"] < t["window_s"]
    n, s = tracefile.step_events({"trace": t}, tracefile.STEP_MODULES)
    assert n == 6                  # 3 calls x 2 chunks of 256
    assert 0 < s <= t["busy_s"] * 1.001
    assert {label for label, _ in t["idle_gaps"]} <= {"bench.dispatch", "bench.await", "none"}
    assert len(t["device_ops"]) == tracefile.TOP
