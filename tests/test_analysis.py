"""Static analyzer tests (DESIGN.md §Analysis): per-rule AST fixtures
(tracer leak, host sync, in-loop sync, rng-in-jit, suppressions), kernel
capability verifier (exact derived int32 bounds, loosened-bound seeded
regression, conservative declarations pass, scratch mismatch), sharding
coverage (clean tree + uncovered-leaf seeded regression), jaxpr/HLO lint
(callback in a compiled loop, f32-literal upcast, donation miss, recompile
budgets), the baseline gate (new fails / baselined passes / stale reported),
and the CLI. The seeded regressions are the acceptance criteria: each pass
must fail the gate on its planted bug."""
import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ast_lint, hlo_lint, kernel_audit, report
from repro.analysis.report import Finding
from repro.kernels import api as kapi


def rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# AST lint
# ---------------------------------------------------------------------------

TRACER_IF = '''
import jax, jax.numpy as jnp

@jax.jit
def f(x):
    y = jnp.sum(x)
    if y:
        return y
    return y + 1
'''

TRACER_INT = '''
import jax, jax.numpy as jnp

@jax.jit
def f(x):
    return int(jnp.max(x))
'''

RNG_IN_JIT = '''
import jax

@jax.jit
def f(x):
    k = jax.random.PRNGKey(0)
    return jax.random.normal(k, x.shape) + x
'''

# the old SelfDrafter.propose shape (pre-PR serve/spec.py): device tokens
# stacked then pulled to host inside the proposal path
OLD_PROPOSE = '''
import jax.numpy as jnp
import numpy as np

def propose(sched):
    outs = []
    for _ in range(4):
        outs.append(sched.step())
    return np.asarray(jnp.stack(outs, axis=1))
'''

SYNC_IN_LOOP = '''
import jax.numpy as jnp
import numpy as np

def drain(xs):
    toks = []
    for x in xs:
        toks.append(np.asarray(jnp.argmax(x)))
    return toks
'''

SCAN_BODY_TRACED = '''
import jax, jax.numpy as jnp

def body(carry, x):
    if jnp.sum(x):
        carry = carry + 1
    return carry, x

def run(xs):
    return jax.lax.scan(body, 0, xs)
'''


class TestAstLint:
    def test_tracer_bool_on_if(self):
        assert rules(ast_lint.lint_source(TRACER_IF)) == ["tracer-bool"]

    def test_tracer_bool_on_int_coercion(self):
        assert rules(ast_lint.lint_source(TRACER_INT)) == ["tracer-bool"]

    def test_tracer_leak_fails_gate(self):
        """Seeded regression: a planted tracer leak fails the gate."""
        findings = ast_lint.lint_source(TRACER_IF)
        assert report.gate(findings, {}) == 1

    def test_rng_in_jit(self):
        assert rules(ast_lint.lint_source(RNG_IN_JIT)) == ["rng-in-jit"]

    def test_old_propose_regression_flagged(self):
        """The pre-PR SelfDrafter.propose host sync is a finding — and a
        planted host sync fails the gate."""
        findings = ast_lint.lint_source(OLD_PROPOSE)
        assert "host-sync" in rules(findings)
        assert report.gate(findings, {}) == 1

    def test_host_sync_in_loop(self):
        assert "host-sync-in-loop" in rules(ast_lint.lint_source(SYNC_IN_LOOP))

    def test_scan_body_is_traced_scope(self):
        """Functions passed to lax.scan are traced even without a jit
        decorator (the combinator pre-pass), regardless of def order."""
        assert "tracer-bool" in rules(ast_lint.lint_source(SCAN_BODY_TRACED))

    def test_suppression_on_line(self):
        src = OLD_PROPOSE.replace(
            "return np.asarray(jnp.stack(outs, axis=1))",
            "return np.asarray(jnp.stack(outs, axis=1))"
            "  # repro: allow(host-sync)")
        assert ast_lint.lint_source(src) == []

    def test_suppression_line_above(self):
        src = OLD_PROPOSE.replace(
            "    return np.asarray(jnp.stack(outs, axis=1))",
            "    # repro: allow(host-sync)\n"
            "    return np.asarray(jnp.stack(outs, axis=1))")
        assert ast_lint.lint_source(src) == []

    def test_suppression_is_rule_specific(self):
        src = OLD_PROPOSE.replace(
            "return np.asarray(jnp.stack(outs, axis=1))",
            "return np.asarray(jnp.stack(outs, axis=1))"
            "  # repro: allow(tracer-bool)")
        assert "host-sync" in rules(ast_lint.lint_source(src))

    def test_attribute_assign_does_not_poison_self(self):
        """`self.x = jnp.f(...)` must not mark `self` device-valued (the
        false positive that would flag every later self.* host read)."""
        src = '''
import jax.numpy as jnp
import numpy as np

class A:
    def set(self):
        self.x = jnp.zeros((4,))

    def get(self):
        return np.asarray(self.host_list)
'''
        assert ast_lint.lint_source(src) == []

    def test_reassignment_clears_device_name(self):
        src = '''
import jax.numpy as jnp
import numpy as np

def f(xs):
    y = jnp.sum(xs)
    y = [1, 2, 3]
    return np.asarray(y)
'''
        assert ast_lint.lint_source(src) == []


# ---------------------------------------------------------------------------
# Kernel capability verifier
# ---------------------------------------------------------------------------

class TestKernelAudit:
    def test_derived_bounds_exact(self):
        """First-principles int32 bounds: 46336 for the linear Fourier
        phase (block-padded 46336 rows x 46335 max index), 32768 for the
        half-integer DCT phase ((2*65535+1)... see kernel_audit)."""
        from repro.kernels import dct_deltaw, fourier_deltaw
        assert kernel_audit.derived_phase_bound(fourier_deltaw.CAPS) == 46336
        assert kernel_audit.derived_phase_bound(dct_deltaw.CAPS) == 32768

    def test_deltaw_vmem_counts_scratch_bases_once(self):
        """FourierFT holds its bases in scratch carried over the layer axis
        (counted once, beside the resident dc block); the DCT kernels
        still double-buffer per-step basis blocks."""
        from repro.kernels import dct_deltaw, fourier_deltaw
        bm = bn = 256
        n = 1024
        tile, entries = bm * bn * 4, 3 * n * 4
        assert kernel_audit.derived_deltaw_vmem(fourier_deltaw.CAPS) == (
            2 * (bm + bn) * n * 4 + 2 * (tile + entries) + 128 * n * 4)
        assert kernel_audit.derived_deltaw_vmem(dct_deltaw.CAPS) == \
            2 * ((bm + bn) * n * 4 + tile + entries)
        deep = dataclasses.replace(
            self._op("fourierft"),
            caps={**fourier_deltaw.CAPS, "l_ref": 4096})
        assert rules(kernel_audit.audit_op(deep)) == ["vmem-over-budget"]

    def test_registry_clean(self):
        assert kernel_audit.run() == []

    def _op(self, method, backend="pallas", op="deltaw"):
        (found,) = [o for o in kapi.all_ops()
                    if (o.op, o.method, o.backend) == (op, method, backend)]
        return found

    def test_loosened_bound_fails_gate(self):
        """Seeded regression: declaring past the derived int32 bound is a
        finding and fails the gate."""
        bad = dataclasses.replace(self._op("fourierft"), max_dim=46400)
        findings = kernel_audit.audit_op(bad)
        assert rules(findings) == ["bound-loosened"]
        assert report.gate(findings, {}) == 1
        bad = dataclasses.replace(self._op("dct"), max_dim=33000)
        assert rules(kernel_audit.audit_op(bad)) == ["bound-loosened"]

    def test_conservative_bound_passes(self):
        """Declared BELOW derived is healthy (DCT ships 32500 < 32768);
        exactly AT derived also passes — only looser fails."""
        dct = self._op("dct")
        assert dct.max_dim == 32500
        assert kernel_audit.audit_op(dct) == []
        at = dataclasses.replace(dct, max_dim=32768)
        assert kernel_audit.audit_op(at) == []
        over = dataclasses.replace(dct, max_dim=32769)
        assert rules(kernel_audit.audit_op(over)) == ["bound-loosened"]

    def test_missing_max_dim_with_caps_flagged(self):
        bad = dataclasses.replace(self._op("fourierft"), max_dim=None)
        assert rules(kernel_audit.audit_op(bad)) == ["bound-missing"]

    def test_paged_scratch_mismatch(self):
        op = self._op("attention", op="paged_attention")
        assert op.caps is not None and kernel_audit.audit_op(op) == []
        caps = dict(op.caps)
        caps["scratch"] = {**caps["scratch"], "acc": ("K", "G", "W")}
        bad = dataclasses.replace(op, caps=caps)
        assert rules(kernel_audit.audit_op(bad)) == ["scratch-mismatch"]

    def test_capless_ops_skipped(self):
        assert kernel_audit.audit_op(
            self._op("fourierft", backend="einsum")) == []

    def test_constant_drift_detected(self, monkeypatch):
        from repro.kernels import ops
        monkeypatch.setattr(ops, "FOURIER_INT32_SAFE_DIM", 46500)
        assert rules(kernel_audit.declared_constants_findings()) \
            == ["constant-drift"]


# ---------------------------------------------------------------------------
# Sharding coverage
# ---------------------------------------------------------------------------

class TestShardingAudit:
    def test_tree_fully_covered(self):
        from repro.analysis import sharding_audit
        assert sharding_audit.run() == []

    def test_uncovered_leaf_flagged(self, monkeypatch):
        """Seeded regression: drop a mamba2 leaf from the replicate table
        and the audit names it (and the gate fails)."""
        from repro.analysis import sharding_audit
        from repro.dist import sharding
        monkeypatch.setattr(sharding, "_REPLICATE",
                            sharding._REPLICATE - {"A_log"})
        findings = sharding_audit.run(methods=("none",),
                                      archs=("mamba2-2.7b",))
        assert rules(findings) == ["uncovered"]
        assert "A_log" in findings[0].where
        assert report.gate(findings, {}) == 1

    def test_rule_kind_classification(self):
        from repro.dist.sharding import rule_kind
        assert rule_kind("base/wq", (2, 64, 64)) == "column"
        assert rule_kind("base/wo__b", (64,)) == "replicate"
        assert rule_kind("base/wi__b", (2, 128)) == "column"
        assert rule_kind("base/embed", (64, 64)) == "row"
        assert rule_kind("base/we_i", (2, 8, 64, 128)) == "expert"
        assert rule_kind("peft/attn.wq/c", (2, 16)) == "replicate"
        assert rule_kind("opt/count", ()) == "scalar"
        assert rule_kind("base/mystery_w", (64, 64)) is None


# ---------------------------------------------------------------------------
# jaxpr / HLO lint
# ---------------------------------------------------------------------------

HOT_LOOP_HLO = '''HloModule m

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4] get-tuple-element(%p), index=1
  %cb = f32[4] custom-call(%x), custom_call_target="xla_python_cpu_callback"
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[4]) tuple(%ni, %cb)
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(32)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (p0: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p0 = (s32[], f32[4]) parameter(0)
  ROOT %w = (s32[], f32[4]) while(%p0), condition=%cond, body=%body
}
'''


class TestHloLint:
    def test_hot_loop_host_transfer_weighted(self):
        """Seeded regression: a host callback in a while body is flagged
        at trip-count multiplicity and fails the gate."""
        findings = hlo_lint.lint_hlo_text(HOT_LOOP_HLO, "fix")
        assert rules(findings) == ["host-transfer-in-loop"]
        assert findings[0].mult == 32
        assert report.gate(findings, {}) == 1

    def test_compiled_callback_flagged(self):
        from jax.experimental import io_callback

        def host(x):
            return np.asarray(x) + 1

        @jax.jit
        def f(x):
            return io_callback(host, jax.ShapeDtypeStruct(x.shape, x.dtype),
                               x)

        txt = f.lower(jnp.zeros((4,), jnp.float32)).compile().as_text()
        assert "host-transfer" in rules(hlo_lint.lint_hlo_text(txt, "f"))

    def test_callback_in_scan_jaxpr(self):
        from jax.experimental import io_callback

        def host(x):
            return np.asarray(x) + 1

        def f(x):
            def body(i, acc):
                return acc + io_callback(
                    host, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return jax.lax.fori_loop(0, 8, body, x)

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32))
        assert "callback-in-loop" in rules(hlo_lint.lint_jaxpr(jaxpr, "f"))

    def test_upcast_f32_literal(self):
        """Seeded regression: an f32 constant dragging a bf16 value into
        f32 is flagged; a weak Python float (which stays bf16, emitting no
        convert) is not."""
        def bad(x):
            return x.astype(jnp.float32) * np.float32(1.5)

        def ok(x):
            return x * 1.5

        x = jnp.zeros((4,), jnp.bfloat16)
        findings = hlo_lint.lint_jaxpr(jax.make_jaxpr(bad)(x), "bad")
        assert rules(findings) == ["upcast-f32-literal"]
        assert report.gate(findings, {}) == 1
        assert hlo_lint.lint_jaxpr(jax.make_jaxpr(ok)(x), "ok") == []

    def test_donation_honored_vs_missed(self):
        """Seeded regression: a donated-but-unusable input (output shape
        differs) drops out of input_output_alias and is flagged."""
        @functools.partial(jax.jit, donate_argnums=(0,))
        def good(x):
            return x + 1

        txt = good.lower(jnp.zeros((128,), jnp.float32)).compile().as_text()
        assert hlo_lint.donation_findings(txt, "good", 1) == []

        @functools.partial(jax.jit, donate_argnums=(0,))
        def wasted(x):
            return x[:32] * 2.0

        txt = wasted.lower(jnp.zeros((128,),
                                     jnp.float32)).compile().as_text()
        findings = hlo_lint.donation_findings(txt, "wasted", 1)
        assert rules(findings) == ["donation-miss"]
        assert report.gate(findings, {}) == 1

    def test_recompile_budget(self):
        assert hlo_lint.recompile_findings({"decode": 1}, {"decode": 1},
                                           "s") == []
        findings = hlo_lint.recompile_findings({"decode": 3}, {"decode": 1},
                                               "s")
        assert rules(findings) == ["recompile-budget"]
        # graphs without a declared bound are skipped, not flagged
        assert hlo_lint.recompile_findings({"prefill": 9}, {}, "s") == []


# ---------------------------------------------------------------------------
# Baseline gate + CLI
# ---------------------------------------------------------------------------

class TestBaselineGate:
    def _f(self, rule="r", where="w"):
        return Finding("ast", rule, where, "msg")

    def test_new_fails_baselined_passes_stale_reported(self):
        f = self._f()
        assert report.gate([f], {}) == 1
        assert report.gate([f], {f.key: "known"}) == 0
        new, stale = report.diff([f], {f.key: "known", "ast:r:gone": "old"})
        assert new == [] and stale == ["ast:r:gone"]
        assert report.gate([f], {f.key: "known", "ast:r:gone": "old"}) == 0

    def test_save_load_roundtrip_keeps_justifications(self, tmp_path):
        path = str(tmp_path / "b.json")
        f1, f2 = self._f(where="w1"), self._f(where="w2")
        report.save_baseline([f1], path)
        bl = report.load_baseline(path)
        assert bl == {f1.key: "TODO: justify"}
        bl[f1.key] = "because"
        report.save_baseline([f1, f2], path, old=bl)
        bl2 = report.load_baseline(path)
        assert bl2[f1.key] == "because"
        assert bl2[f2.key] == "TODO: justify"

    def test_version_check(self, tmp_path):
        path = tmp_path / "b.json"
        path.write_text('{"version": 99, "findings": {}}')
        with pytest.raises(ValueError):
            report.load_baseline(str(path))

    def test_missing_baseline_is_empty(self, tmp_path):
        assert report.load_baseline(str(tmp_path / "nope.json")) == {}

    def test_committed_baseline_loads_and_is_justified(self):
        bl = report.load_baseline()
        for key, justification in bl.items():
            assert justification and "TODO" not in justification, key

    def test_cli_gate_and_update(self, tmp_path, capsys):
        from repro.analysis.__main__ import main
        fix = tmp_path / "bad.py"
        fix.write_text(OLD_PROPOSE)
        bl = str(tmp_path / "baseline.json")
        rep = str(tmp_path / "report.json")
        assert main(["--ast", str(fix), "--baseline", bl,
                     "--json", rep]) == 1
        data = json.loads(open(rep).read())
        assert data["n_new"] >= 1 and data["n_findings"] == data["n_new"]
        assert any("host-sync" in k for k in data["new"])
        assert main(["--ast", str(fix), "--baseline", bl,
                     "--update-baseline"]) == 0
        assert main(["--ast", str(fix), "--baseline", bl]) == 0
        out = capsys.readouterr().out
        assert "baselined finding" in out
