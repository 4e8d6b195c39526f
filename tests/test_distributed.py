"""Distributed substrate: delta reduction, checkpoint, fault tolerance,
sharding rules."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import (
    Checkpointer,
    latest_step,
    restore_pytree,
    save_pytree,
)
from repro.distributed.collectives import tree_psum_batch
from repro.distributed.fault_tolerance import (
    HeartbeatMonitor,
    StragglerPolicy,
    run_with_restarts,
)


class TestDeltaPsum:
    def _deltas(self, b=16, seed=0):
        rng = np.random.default_rng(seed)
        return (
            jnp.asarray(rng.integers(-2, 3, (b, 12, 34)), jnp.int32),
            jnp.asarray(rng.integers(-1, 2, (b, 10, 12)), jnp.int32),
        )

    def test_plain_sum_without_mesh(self):
        ta, w = self._deltas()
        sa, sw = tree_psum_batch((ta, w))
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(ta).sum(0))
        np.testing.assert_array_equal(np.asarray(sw), np.asarray(w).sum(0))

    def test_single_device_mesh_matches_plain_sum(self):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        ta, w = self._deltas(seed=1)
        sa, sw = tree_psum_batch((ta, w), mesh=mesh, axis="data")
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(ta).sum(0))
        np.testing.assert_array_equal(np.asarray(sw), np.asarray(w).sum(0))

    def test_tm_delta_psum_multidevice_subprocess(self):
        """The exact integer delta reduction on an 8-virtual-device CPU
        mesh is bit-identical to the single-device sum — the TM
        data-parallel training contract."""
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.distributed.collectives import tree_psum_batch
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(0)
ta = jnp.asarray(rng.integers(-2, 3, (64, 12, 34)), jnp.int32)
w = jnp.asarray(rng.integers(-1, 2, (64, 10, 12)), jnp.int32)
sa, sw = jax.jit(lambda t: tree_psum_batch(t, mesh=mesh, axis="data"))((ta, w))
np.testing.assert_array_equal(np.asarray(sa), np.asarray(ta).sum(0))
np.testing.assert_array_equal(np.asarray(sw), np.asarray(w).sum(0))
print("OK")
"""
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=300,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "OK" in r.stdout


class TestCheckpoint:
    def _tree(self, seed=0):
        rng = np.random.default_rng(seed)
        return {
            "params": {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.bfloat16)},
            "opt": {"step": jnp.int32(7), "m": jnp.asarray(rng.standard_normal(3))},
        }

    def test_roundtrip(self, tmp_path):
        tree = self._tree()
        save_pytree(tree, str(tmp_path), 7, extra={"epoch": 2})
        out, step, extra = restore_pytree(tree, str(tmp_path))
        assert step == 7 and extra == {"epoch": 2}
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_only_committed_restored(self, tmp_path):
        tree = self._tree()
        save_pytree(tree, str(tmp_path), 5)
        # fake a torn write at step 9
        torn = tmp_path / "step_00000009"
        torn.mkdir()
        (torn / "manifest.json").write_text("{}")
        assert latest_step(str(tmp_path)) == 5

    def test_async_checkpointer_gc(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=2)
        tree = self._tree()
        for s in [1, 2, 3, 4]:
            ck.save(tree, s)
        ck.wait()
        steps = sorted(
            int(d[5:]) for d in os.listdir(tmp_path) if d.startswith("step_")
        )
        assert steps == [3, 4]

    def test_restore_rejects_shape_change(self, tmp_path):
        tree = self._tree()
        save_pytree(tree, str(tmp_path), 1)
        bad = {
            "params": {"w": jnp.zeros((9, 4), jnp.bfloat16)},
            "opt": tree["opt"],
        }
        with pytest.raises(ValueError):
            restore_pytree(bad, str(tmp_path))

    def test_async_save_failure_surfaces_on_wait(self, tmp_path, monkeypatch):
        """Regression: the save thread used to swallow exceptions — wait()
        reported success and a restart silently resumed from an older
        step.  The failure must re-raise on the next wait()."""
        import repro.checkpoint.checkpointer as ckpt_mod

        ck = Checkpointer(str(tmp_path))
        tree = self._tree()
        ck.save(tree, 1)
        ck.wait()                                 # healthy save is clean

        def boom(*a, **kw):
            raise OSError("disk full")

        monkeypatch.setattr(ckpt_mod, "save_pytree", boom)
        ck.save(tree, 2)
        with pytest.raises(OSError, match="disk full"):
            ck.wait()
        # the error is consumed exactly once; the checkpointer stays usable
        ck.wait()
        monkeypatch.undo()
        ck.save(tree, 3)
        ck.wait()
        assert latest_step(str(tmp_path)) == 3

    def test_async_save_failure_surfaces_on_next_save(self, tmp_path, monkeypatch):
        """save() joins the previous save first, so a failed save also
        surfaces there — before the next checkpoint is dispatched."""
        import repro.checkpoint.checkpointer as ckpt_mod

        ck = Checkpointer(str(tmp_path))
        tree = self._tree()
        monkeypatch.setattr(
            ckpt_mod, "save_pytree",
            lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("torn write")),
        )
        ck.save(tree, 1)
        with pytest.raises(RuntimeError, match="torn write"):
            ck.save(tree, 2)

    def test_malformed_step_dirs_skipped(self, tmp_path):
        """Regression: a stray non-numeric step_* directory crashed
        latest_step and Checkpointer._gc on int()."""
        tree = self._tree()
        save_pytree(tree, str(tmp_path), 3)
        for junk in ["step_backup", "step_1a2b", "step_"]:
            d = tmp_path / junk
            d.mkdir()
            (d / "COMMITTED").write_text("")      # committed but malformed
        assert latest_step(str(tmp_path)) == 3

        ck = Checkpointer(str(tmp_path), keep=2)
        for s in [4, 5, 6]:
            ck.save(tree, s)
        ck.wait()                                 # _gc must not crash
        steps = sorted(
            d for d in os.listdir(tmp_path) if d.startswith("step_")
        )
        assert steps == [
            "step_", "step_00000005", "step_00000006", "step_1a2b",
            "step_backup",
        ]


class TestFaultTolerance:
    def test_straggler_policy_escalates(self):
        p = StragglerPolicy(factor=3.0, window=16, tolerance=3)
        for _ in range(16):
            assert p.observe(1.0) == "ok"
        assert p.observe(10.0) == "straggler"
        assert p.observe(10.0) == "straggler"
        assert p.observe(10.0) == "reshard"
        # in-window recovery (before reshard) still resets strikes
        p2 = StragglerPolicy(factor=3.0, window=16, tolerance=3)
        for _ in range(16):
            p2.observe(1.0)
        assert p2.observe(10.0) == "straggler"
        assert p2.observe(1.0) == "ok"
        assert p2.observe(10.0) == "straggler"

    def test_straggler_policy_resets_after_reshard(self):
        """Regression: 'reshard' used to latch — every later straggler
        event escalated straight back to 'reshard' and pre-reshard
        (straggler-inflated) durations kept polluting the median.  The
        intervention now clears strikes AND history."""
        p = StragglerPolicy(factor=3.0, window=16, tolerance=3)
        for _ in range(16):
            p.observe(1.0)
        assert [p.observe(10.0) for _ in range(3)] == [
            "straggler", "straggler", "reshard"
        ]
        # History cleared: the policy re-warms on post-reshard step times
        # (2.0 s/step on the rebuilt, smaller mesh) instead of judging
        # them against the old 1.0 s median.
        assert p.median is None
        for _ in range(16):
            assert p.observe(2.0) == "ok"
        assert p.median == pytest.approx(2.0)
        # A second full escalate->reshard cycle behaves like the first:
        # one event is 'straggler', not an instant 'reshard'.
        assert p.observe(20.0) == "straggler"
        assert p.observe(20.0) == "straggler"
        assert p.observe(20.0) == "reshard"
        assert p.median is None

    def test_heartbeat_dead_hosts(self):
        hb = HeartbeatMonitor(timeout=10.0)
        hb.beat("h0", now=0.0)
        hb.beat("h1", now=0.0)
        hb.beat("h0", now=8.0)
        assert hb.dead_hosts(now=12.0) == ["h1"]
        assert not hb.healthy(now=12.0)

    def test_run_with_restarts_recovers(self):
        log = {"saved": [], "failed_at": []}
        state = {"ckpt": 0}

        def step_fn(step):
            if step == 5 and not log["failed_at"]:
                log["failed_at"].append(step)
                raise RuntimeError("node lost")

        def save_fn(step):
            state["ckpt"] = step
            log["saved"].append(step)

        def restore_fn():
            return state["ckpt"]

        stats = run_with_restarts(
            step_fn, start_step=0, total_steps=10, save_fn=save_fn,
            restore_fn=restore_fn, checkpoint_every=2, max_restarts=2,
        )
        assert stats.restarts == 1
        assert stats.resumed_from == [4]
        assert state["ckpt"] == 10

    def test_run_with_restarts_gives_up(self):
        def step_fn(step):
            raise RuntimeError("always broken")

        with pytest.raises(RuntimeError):
            run_with_restarts(
                step_fn, start_step=0, total_steps=3,
                save_fn=lambda s: None, restore_fn=lambda: 0,
                checkpoint_every=10, max_restarts=2,
            )

    def test_restart_budget_resets_after_checkpointed_progress(self):
        """Regression: the restart budget counted failures over the whole
        job lifetime, so a long-lived run died on its (max_restarts+1)-th
        transient failure even with checkpointed progress in between.
        The budget now bounds *consecutive* failures: 4 transient
        failures spread across a 40-step run survive max_restarts=1."""
        state = {"ckpt": 0}
        failed_at = set()

        def step_fn(step):
            if step in (5, 15, 25, 35) and step not in failed_at:
                failed_at.add(step)
                raise RuntimeError(f"transient failure at {step}")

        def save_fn(step):
            state["ckpt"] = step

        stats = run_with_restarts(
            step_fn, start_step=0, total_steps=40, save_fn=save_fn,
            restore_fn=lambda: state["ckpt"], checkpoint_every=2,
            max_restarts=1,
        )
        assert stats.restarts == 4           # lifetime total still reported
        assert stats.resumed_from == [4, 14, 24, 34]
        assert state["ckpt"] == 40

    def test_restart_budget_still_bounds_crash_loops(self):
        """A failure loop with NO checkpointed progress between failures
        must still give up after max_restarts, even when an earlier save
        reset the budget."""
        state = {"ckpt": 0}
        calls = {"n": 0}

        def step_fn(step):
            if step >= 6:                    # permanent breakage at step 6
                calls["n"] += 1
                raise RuntimeError("stuck")

        def save_fn(step):
            state["ckpt"] = step

        with pytest.raises(RuntimeError, match="stuck"):
            run_with_restarts(
                step_fn, start_step=0, total_steps=10, save_fn=save_fn,
                restore_fn=lambda: state["ckpt"], checkpoint_every=2,
                max_restarts=3,
            )
        assert calls["n"] == 4               # 3 retries + the final raise
        assert state["ckpt"] == 6            # progress up to the breakage

    def test_heartbeat_expect_declares_silent_from_birth_hosts_dead(self):
        """Regression: only hosts that beat at least once were tracked,
        so a host that died during bring-up (never beat) was reported
        healthy forever.  expect() starts every roster host's silence
        clock, so silent-from-birth hosts age into dead_hosts."""
        hb = HeartbeatMonitor(timeout=10.0)
        hb.expect(["h0", "h1", "h2"], now=0.0)
        hb.beat("h0", now=8.0)
        hb.beat("h1", now=8.0)
        # h2 never beat: dead once the timeout elapses from expect().
        assert hb.dead_hosts(now=12.0) == ["h2"]
        assert not hb.healthy(now=12.0)
        # expect() never regresses a clock: re-expecting the roster keeps
        # h0/h1's latest beats (silence 9 s at t=17, still alive) AND
        # keeps h2 dead (its clock stays at the original expect, not the
        # re-expect).
        hb.expect(["h0", "h1", "h2"], now=12.0)
        assert hb.dead_hosts(now=17.0) == ["h2"]

    def test_run_with_restarts_restore_failure_consumes_budget(self):
        """Regression: restore_fn raising escaped the restart loop
        without consuming budget — a corrupt checkpoint turned one step
        failure into an instant job abort regardless of max_restarts.
        Recovery failures now retry under the same budget."""
        state = {"ckpt": 0, "restores": 0}
        failed = set()

        def step_fn(step):
            if step == 3 and step not in failed:
                failed.add(step)
                raise RuntimeError("node lost")

        def restore_fn():
            state["restores"] += 1
            if state["restores"] == 1:       # first restore hits a bad ckpt
                raise IOError("checkpoint unreachable")
            return state["ckpt"]

        stats = run_with_restarts(
            step_fn, start_step=0, total_steps=6,
            save_fn=lambda s: state.__setitem__("ckpt", s),
            restore_fn=restore_fn, checkpoint_every=2, max_restarts=3,
        )
        # step failure + failed restore both consumed budget; the retry
        # restored and the run completed.
        assert stats.restarts == 2
        assert stats.resumed_from == [2]
        assert state["ckpt"] == 6

    def test_run_with_restarts_persistent_restore_failure_exhausts_budget(self):
        """A restore that NEVER succeeds must exhaust max_restarts and
        surface the recovery error, not loop forever."""
        calls = {"restores": 0}

        def step_fn(step):
            raise RuntimeError("node lost")

        def restore_fn():
            calls["restores"] += 1
            raise IOError("checkpoint gone")

        with pytest.raises(IOError, match="checkpoint gone"):
            run_with_restarts(
                step_fn, start_step=0, total_steps=5,
                save_fn=lambda s: None, restore_fn=restore_fn,
                checkpoint_every=10, max_restarts=3,
            )
        # budget: 1 step failure + up to max_restarts recovery attempts
        assert calls["restores"] == 3

    def test_run_with_restarts_on_restart_failure_consumes_budget(self):
        """on_restart (mesh teardown) raising is a recovery failure too:
        budgeted and retried, not an escape hatch."""
        state = {"ckpt": 0}
        hooks = {"calls": 0}
        failed = set()

        def step_fn(step):
            if step == 2 and step not in failed:
                failed.add(step)
                raise RuntimeError("node lost")

        def on_restart(e):
            hooks["calls"] += 1
            if hooks["calls"] == 1:
                raise RuntimeError("mesh teardown failed")

        stats = run_with_restarts(
            step_fn, start_step=0, total_steps=4,
            save_fn=lambda s: state.__setitem__("ckpt", s),
            restore_fn=lambda: state["ckpt"], checkpoint_every=2,
            max_restarts=3, on_restart=on_restart,
        )
        assert stats.restarts == 2
        assert hooks["calls"] == 2
        assert state["ckpt"] == 4


class TestShardingRules:
    def test_logical_rules_resolve_per_mesh(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.sharding.partition import spec

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        s = spec(("batch", None, "clause"), mesh)
        assert s == P("data", None, None)   # clause axis absent -> dropped
        mesh2 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        assert spec(("clause", None), mesh2) == P("model", None)
