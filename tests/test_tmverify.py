"""tools/tmverify: every rule pinned with positive + negative fixtures,
the waiver baseline machinery, the committed-report freshness gate, and
the clean full run over the real serve/train paths (the acceptance gate:
every registered (path x form x bucket) step plus the trainer epoch step
verifies under TM401-TM405).
"""

import dataclasses
import json
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tools.tmverify.analyses import (
    aliased_output_count,
    audit_registry_path,
    check_donation,
    check_host_transfers,
    forbidden_primitives,
)
from tools.tmverify.core import Baseline, Finding, VerifyResult
from tools.tmverify.intervals import Interval, analyze_fn, dtype_interval
from tools.tmverify.pallas_check import PallasCapture, audit_capture
from tools.tmverify.report import render_report
from tools.tmverify.run import run_verify
from tools.tmverify.targets import StepTarget, VerifyConfig, buckets_for

REPO = Path(__file__).resolve().parents[1]
BASELINE_PATH = REPO / "tools/tmverify/baseline.json"
REPORT_PATH = REPO / "tools/tmverify/REPORT.md"


def fresh_result() -> VerifyResult:
    return VerifyResult(
        findings=[], suppressed=[], stale_baseline=[], targets=[], checks=0
    )


@pytest.fixture(scope="module")
def verify_run():
    """One full verify of the committed tree, shared by the positive
    tests (the run is the expensive part: ~100 traces + one compile)."""
    vcfg = VerifyConfig()
    baseline = Baseline.load(BASELINE_PATH)
    return run_verify(vcfg, baseline), vcfg, baseline


# --------------------------------------------------------------------------
# Full-run acceptance
# --------------------------------------------------------------------------


class TestFullRun:
    def test_committed_tree_is_clean(self, verify_run):
        result, _, _ = verify_run
        assert result.ok, [f.render() for f in result.findings]
        assert not result.stale_baseline

    def test_enumerates_every_path_form_bucket(self, verify_run):
        from repro.serve.paths import available_paths

        result, vcfg, _ = verify_run
        serve = [t for t in result.targets if t.startswith("serve:")]
        paths = available_paths()
        n_buckets = len(buckets_for(vcfg.max_batch))
        assert len(serve) == len(paths) * 2 * n_buckets
        for name in paths:
            for form in ("literals", "raw"):
                for b in buckets_for(vcfg.max_batch):
                    assert f"serve:{name}:{form}:b{b}" in serve
        assert "train:epoch" in result.targets

    def test_every_rule_ran(self, verify_run):
        result, _, _ = verify_run
        assert sorted(result.summary) == [
            "TM401", "TM402", "TM403", "TM404", "TM405"
        ]
        assert result.checks > 100

    def test_committed_report_is_fresh(self, verify_run):
        result, vcfg, _ = verify_run
        assert render_report(result, vcfg) == REPORT_PATH.read_text(
            encoding="utf-8"
        ), (
            "tools/tmverify/REPORT.md is stale; regenerate with "
            "`python -m tools.tmverify src/repro --report > "
            "tools/tmverify/REPORT.md`"
        )

    def test_cli_list_rules(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.tmverify", "--list-rules"],
            cwd=REPO, capture_output=True, text=True,
        )
        assert proc.returncode == 0
        for rule in ("TM401", "TM402", "TM403", "TM404", "TM405"):
            assert rule in proc.stdout


# --------------------------------------------------------------------------
# TM401 donation audit
# --------------------------------------------------------------------------


class TestTM401:
    def _target(self, fn, arg, donated: int, kind="serve") -> StepTarget:
        tr = fn.trace(arg)
        return StepTarget(
            name="fixture:donate", kind=kind, path_name=None, form=None,
            bucket=None, jaxpr=tr.jaxpr, donated_leaves=donated, traced=tr,
        )

    def test_honoured_donation_passes(self):
        f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
        t = self._target(f, jnp.zeros((8,), jnp.float32), donated=1)
        assert aliased_output_count(t.lowered_text()) == 1
        result = fresh_result()
        check_donation([t], result, Baseline.empty())
        assert result.ok

    def test_dropped_donation_flagged(self):
        # Donated input cannot alias the scalar output: XLA silently
        # drops the donation — exactly what TM401 exists to catch.
        f = jax.jit(lambda x: x.sum(), donate_argnums=(0,))
        t = self._target(f, jnp.zeros((8,), jnp.float32), donated=1)
        assert aliased_output_count(t.lowered_text()) == 0
        result = fresh_result()
        check_donation([t], result, Baseline.empty())
        assert [f_.rule for f_ in result.findings] == ["TM401"]
        assert result.findings[0].key == "dropped:0of1"


# --------------------------------------------------------------------------
# TM402 host-transfer freedom
# --------------------------------------------------------------------------


class TestTM402:
    def test_pure_graph_passes(self):
        closed = jax.make_jaxpr(lambda x: (x * 2).sum())(jnp.ones(4))
        assert forbidden_primitives(closed.jaxpr) == []

    def test_callback_flagged(self):
        def bad(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        closed = jax.make_jaxpr(bad)(jnp.ones(4))
        # jax.debug.print lowers to the debug_print primitive (JAX 0.9),
        # which carries the debug effect.
        assert forbidden_primitives(closed.jaxpr) == ["debug_print"]

        t = StepTarget(
            name="fixture:callback", kind="serve", path_name=None,
            form=None, bucket=None, jaxpr=closed, donated_leaves=0,
            traced=None,
        )
        result = fresh_result()
        check_host_transfers([t], result, Baseline.empty())
        assert [f.rule for f in result.findings] == ["TM402"]

    @pytest.mark.parametrize("kind", ["debug_callback", "io_callback"])
    def test_effectful_host_transfers_flagged(self, kind):
        from jax.experimental import io_callback

        def bad(x):
            if kind == "debug_callback":
                jax.debug.callback(lambda v: None, x)
            else:
                x = io_callback(lambda v: v, jax.ShapeDtypeStruct((4,), jnp.float32), x)
            return x * 2

        closed = jax.make_jaxpr(bad)(jnp.ones(4))
        assert forbidden_primitives(closed.jaxpr) == [kind]

    def test_nested_jaxprs_are_walked(self):
        # The callback hides inside a jitted sub-call; the walk must
        # recurse through the pjit body to see it.
        inner = jax.jit(lambda x: jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((4,), jnp.float32), x
        ))
        closed = jax.make_jaxpr(lambda x: inner(x) + 1)(jnp.ones(4))
        assert forbidden_primitives(closed.jaxpr)


# --------------------------------------------------------------------------
# TM403 recompile-key audit
# --------------------------------------------------------------------------


def fake_path(**kw):
    defaults = dict(
        name="fixture", input_form="packed", tunable=((),), fallback=None,
        ingress_spec=lambda spec: spec,
    )
    defaults.update(kw)
    ns = types.SimpleNamespace(**{
        k: v for k, v in defaults.items() if k != "ingress_spec"
    })
    ns.ingress_spec = defaults["ingress_spec"]
    return ns


class TestTM403:
    SPEC = None  # a hashable stand-in is enough for the fixtures

    def audit(self, path, cap=128, n_buckets=9):
        return audit_registry_path(
            path, self.SPEC, n_buckets=n_buckets, n_forms=2, cap=cap
        )

    def test_real_registry_is_bounded(self):
        from repro.core.patches import PatchSpec
        from repro.serve.paths import available_paths, get_path

        spec = PatchSpec(8, 8, 4, 4)
        for name in available_paths():
            findings, card = audit_registry_path(
                get_path(name), spec, n_buckets=9, n_forms=2, cap=128
            )
            assert findings == [], [f.render() for f in findings]
            assert card <= 128

    def test_list_tunable_flagged(self):
        findings, _ = self.audit(fake_path(tunable=[()]))
        assert any(f.key == "tunable:not-tuple" for f in findings)

    def test_unhashable_param_value_flagged(self):
        findings, _ = self.audit(
            fake_path(tunable=((("block_b", [8, 16]),),))
        )
        assert any(f.key == "params:0:unhashable" for f in findings)

    def test_malformed_param_set_flagged(self):
        findings, _ = self.audit(fake_path(tunable=(("block_b", 16),)))
        assert any("malformed" in f.key for f in findings)

    def test_unhashable_ingress_spec_flagged(self):
        findings, _ = self.audit(fake_path(ingress_spec=lambda spec: []))
        assert any(f.key == "ingress:unhashable" for f in findings)

    def test_unregistered_fallback_flagged(self):
        findings, _ = self.audit(fake_path(fallback="no_such_path"))
        assert any(f.key == "fallback:unregistered" for f in findings)

    def test_unbounded_cardinality_flagged(self):
        many = tuple(((("block_b", 8 * i),)) for i in range(1, 30))
        findings, card = self.audit(fake_path(tunable=many), cap=100)
        assert card == 9 * 29
        assert any(f.key.startswith("cardinality:") for f in findings)


# --------------------------------------------------------------------------
# TM404 interval analysis
# --------------------------------------------------------------------------


class TestTM404:
    S = jax.ShapeDtypeStruct

    def test_int32_class_sum_proven_safe(self):
        # The envelope proof in miniature: 127 * C ones into int32.
        def f(fired, w):
            return jax.lax.dot_general(
                fired.astype(jnp.int8), w.astype(jnp.int8),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )

        findings, stats = analyze_fn(
            f, [self.S((4, 1024), jnp.uint8), self.S((64, 1024), jnp.int8)],
            [Interval(0, 1), Interval(-127, 127)], "fixture:class_sum",
        )
        assert findings == []
        assert stats.widest_int == Interval(-130048, 130048)

    def test_int8_accumulator_overflow_flagged(self):
        findings, _ = analyze_fn(
            lambda x: jnp.sum(x, axis=0, dtype=jnp.int8),
            [self.S((300,), jnp.int8)], [Interval(0, 1)], "fixture:sum8",
        )
        assert [f.rule for f in findings] == ["TM404"]
        assert "overflows int8" in findings[0].message

    def test_narrowing_convert_flagged(self):
        findings, _ = analyze_fn(
            lambda x: x.astype(jnp.int8),
            [self.S((4,), jnp.int32)], [Interval(0, 300)], "fixture:narrow",
        )
        assert [f.key.endswith("narrowing") for f in findings] == [True]

    def test_fp32_exactness_loss_flagged(self):
        findings, _ = analyze_fn(
            lambda x: x.astype(jnp.float32),
            [self.S((4,), jnp.int32)], [Interval(0, 1 << 25)],
            "fixture:inexact",
        )
        assert [f.rule for f in findings] == ["TM404"]
        assert "exact-integer bound 16777216" in findings[0].message

    def test_popcount_chain_bound(self):
        # sum of W=256 popcounts of uint32 words: proven <= 8192.
        def f(w):
            return jnp.sum(
                jax.lax.population_count(w).astype(jnp.int32), axis=-1
            )

        findings, stats = analyze_fn(
            f, [self.S((4, 256), jnp.uint32)],
            [Interval(0, (1 << 32) - 1)], "fixture:popcount",
        )
        assert findings == []

    def test_folded_convolution_bound(self):
        # bits [0, 1] against a {-1, 0, 1} kernel over a 4x4 window of 3
        # features: each output sums 48 products, so |v| <= 48; the same
        # window at 2^21 features would pass fp32's exact range.
        def f(x, k):
            return jax.lax.conv_general_dilated(
                x.astype(jnp.bfloat16), k.astype(jnp.bfloat16), (1, 1),
                "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)

        findings, stats = analyze_fn(
            f, [self.S((2, 8, 8, 3), jnp.uint8), self.S((4, 4, 3, 5), jnp.int8)],
            [Interval(0, 1), Interval(-1, 1)], "fixture:conv",
        )
        assert findings == []
        assert stats.widest_int == Interval(-48, 48)
        findings, _ = analyze_fn(
            f, [self.S((1, 8, 8, 1 << 21), jnp.uint8),
                self.S((4, 4, 1 << 21, 1), jnp.int8)],
            [Interval(0, 1), Interval(-1, 1)], "fixture:conv-wide",
        )
        assert any(f.key.endswith("conv_general_dilated:inexact") for f in findings)

    def test_dtype_interval(self):
        assert dtype_interval(jnp.int8) == Interval(-128, 127)
        assert dtype_interval(jnp.uint32) == Interval(0, (1 << 32) - 1)
        assert dtype_interval(jnp.float32) == Interval(-(1 << 24), 1 << 24)


# --------------------------------------------------------------------------
# TM405 Pallas grid/VMEM audit
# --------------------------------------------------------------------------


def block_spec(shape, index_map):
    return types.SimpleNamespace(block_shape=shape, index_map=index_map)


class TestTM405:
    def test_exact_cover_passes(self):
        cap = PallasCapture(
            label="fixture", grid=(3, 2),
            in_specs=[block_spec((8, 128), lambda i, j: (i, j))],
            out_specs=[], out_shapes=[], scratch=[],
            operand_shapes=[(24, 256)],
        )
        findings, footprint = audit_capture(cap, budget=16 << 20)
        assert findings == []
        assert footprint == 2 * 8 * 128 * 4

    def test_undersized_grid_flagged(self):
        # 24 rows need 3 blocks of 8; a grid of 2 drops the last tile.
        cap = PallasCapture(
            label="fixture", grid=(2,),
            in_specs=[block_spec((8, 128), lambda i: (i, 0))],
            out_specs=[], out_shapes=[], scratch=[],
            operand_shapes=[(24, 128)],
        )
        findings, _ = audit_capture(cap, budget=16 << 20)
        assert any(f.key == "in0:axis0:cover" for f in findings)

    def test_unpadded_extent_flagged(self):
        cap = PallasCapture(
            label="fixture", grid=(2,),
            in_specs=[block_spec((8, 128), lambda i: (i, 0))],
            out_specs=[], out_shapes=[], scratch=[],
            operand_shapes=[(12, 128)],
        )
        findings, _ = audit_capture(cap, budget=16 << 20)
        assert any(f.key == "in0:axis0:unpadded" for f in findings)

    def test_over_budget_footprint_flagged(self):
        cap = PallasCapture(
            label="fixture", grid=(1,),
            in_specs=[block_spec((4096, 4096), lambda i: (0, 0))],
            out_specs=[], out_shapes=[],
            scratch=[((4096, 4096), jnp.int32)],
            operand_shapes=[(4096, 4096)],
        )
        findings, footprint = audit_capture(cap, budget=16 << 20)
        assert any(f.key.startswith("vmem:") for f in findings)
        assert footprint == 3 * 4096 * 4096 * 4

    def test_clamped_blocks_match_dispatch(self):
        # clamp_block is shared with ops.py so the audit sees dispatch's
        # real block shapes: a 3-row batch never pays for a 128-row tile.
        from repro.kernels.shapes import clamp_block

        assert clamp_block(128, 3, 8) == 8
        assert clamp_block(8, 4096, 8) == 8
        assert clamp_block(128, 1024, 128) == 128


# --------------------------------------------------------------------------
# Baseline machinery
# --------------------------------------------------------------------------


class TestBaseline:
    FINDING = Finding("TM401", "serve:x:raw:b8", "dropped:0of1", "msg")

    def test_waiver_suppresses(self):
        b = Baseline([{
            "rule": "TM401", "target": "serve:x:raw:b8",
            "key": "dropped:0of1", "justification": "accepted for reasons",
        }])
        result = fresh_result()
        result.add(b, self.FINDING)
        assert result.ok
        assert len(result.suppressed) == 1
        assert b.stale_entries() == []

    def test_missing_justification_rejected(self):
        with pytest.raises(ValueError, match="justification"):
            Baseline([{
                "rule": "TM401", "target": "t", "key": "k",
                "justification": "  ",
            }])

    def test_stale_waiver_reported(self):
        b = Baseline([{
            "rule": "TM405", "target": "pallas:gone", "key": "vmem:1",
            "justification": "kernel was removed",
        }])
        result = fresh_result()
        result.add(b, self.FINDING)  # does not match the waiver
        assert not result.ok
        assert len(b.stale_entries()) == 1

    def test_committed_baseline_loads(self):
        data = json.loads(BASELINE_PATH.read_text(encoding="utf-8"))
        assert data["version"] == 1
        Baseline.load(BASELINE_PATH)  # justification contract holds


# --------------------------------------------------------------------------
# Target enumeration helpers
# --------------------------------------------------------------------------


class TestTargets:
    def test_buckets_cover_pow2_range(self):
        assert buckets_for(32) == (1, 2, 4, 8, 16, 32)
        assert buckets_for(1) == (1,)
        assert buckets_for(256) == (1, 2, 4, 8, 16, 32, 64, 128, 256)
