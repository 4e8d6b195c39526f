"""The harness end to end on the CPU at a tiny size, through the test entry
``cell.run_cell`` (``bench/run.py`` itself refuses a CPU), and with the
timed path broken underneath, where ``correct`` has to come out false."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import harness
from cell import run_cell

ROOT = harness.ROOT
#: Tiny traffic: the cells' own mixes at a size a CPU test run holds.
TINY = {
    "engine": dict(frames_per_request=512, pool_requests=2, check_sample=256),
    "service": dict(rate_per_s=150, pool_frames=512, check_sample=96, grace_s=30),
}


def _run(name, capsys, seconds=1.0, trace=False):
    spec = harness.load_spec()
    wl = harness.find_workload(spec, name)
    traffic = harness.load_traffic(wl["traffic"])
    traffic.update(TINY[traffic["entry"]])
    run_cell(name, 2**31 + 17, seconds, trace, jax.devices()[:wl["chips"]],
             time.monotonic(), traffic=traffic, peaks=harness.peaks_for("TPU v5 lite"))
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("name", ["mnist-sensors", "mnist-bulk", "mnist-bulk-mesh4"])
def test_cell_runs_correct(name, capsys):
    res = _run(name, capsys)
    assert res["correct"] is True, res
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    wl = harness.find_workload(harness.load_spec(), name)
    assert res["device"]["count"] == wl["chips"]
    want = {m["name"] for m in harness.cell_metrics(harness.load_spec(), name, False)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


def _alter_answers(step):
    """A class sum altered where the step produces it."""
    def broken(*a, **kw):
        preds, sums = step(*a, **kw)
        return preds, sums + jnp.asarray([1] + [0] * (sums.shape[1] - 1), sums.dtype)
    return broken


def _drop_half(step):
    """The second half of each chunk left out: its rows come back zero."""
    def broken(*a, **kw):
        preds, sums = step(*a, **kw)
        keep = (jnp.arange(sums.shape[0]) < sums.shape[0] // 2)[:, None]
        return jnp.where(keep[:, 0], preds, 0), jnp.where(keep, sums, 0)
    return broken


def _drop_exchange(step):
    """The gather between chips left out: only the first shard's rows
    come back, the other chips' rows stay zero."""
    def broken(*a, **kw):
        preds, sums = step(*a, **kw)
        shard = sums.shape[0] // 4
        keep = (jnp.arange(sums.shape[0]) < shard)[:, None]
        return jnp.where(keep[:, 0], preds, 0), jnp.where(keep, sums, 0)
    return broken


@pytest.mark.parametrize("name,target,fault", [
    ("mnist-sensors", "classify_raw_step", _alter_answers),
    ("mnist-bulk", "classify_raw_step", _alter_answers),
    ("mnist-bulk", "classify_raw_step", _drop_half),
    ("mnist-bulk-mesh4", "classify_step_meshed", _alter_answers),
    ("mnist-bulk-mesh4", "classify_step_meshed", _drop_exchange),
])
def test_broken_timed_path_is_not_correct(name, target, fault, capsys, monkeypatch):
    import repro.serve.engine as eng

    monkeypatch.setattr(eng, target, fault(getattr(eng, target)))
    res = _run(name, capsys)
    assert res["correct"] is False, res
    assert res["checks"]["rows_wrong"]["value"] > 0


def test_trace_run_reads_no_device_metric_on_cpu(capsys):
    res = _run("mnist-bulk", capsys, trace=True)
    assert res["correct"] is True
    assert res["metrics"] == {}          # no device plane: nothing to read


def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist-bulk", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
