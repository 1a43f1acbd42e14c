"""Distribution tests: sharding rules, HLO analyzer (validated against
known-truth programs), gradient compression (error-feedback property) and
MoE routing invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.dist import hlo
from repro.models import moe as moe_mod
from repro.configs.base import MoEConfig


class TestHloAnalyzer:
    def _stats(self, fn, *args):
        txt = jax.jit(fn).lower(*args).compile().as_text()
        return hlo.analyze_module(txt)

    def test_plain_matmul_exact(self):
        A = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
        s = self._stats(lambda a: a @ a, A)
        assert abs(s.dot_flops - 2 * 128 ** 3) / (2 * 128 ** 3) < 0.01

    def test_scan_trip_count_scaling(self):
        A = jax.random.normal(jax.random.PRNGKey(0), (128, 128))

        def f(a):
            out, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ a), None), a,
                                  None, length=8)
            return out
        s = self._stats(f, A)
        truth = 8 * 2 * 128 ** 3
        assert abs(s.dot_flops - truth) / truth < 0.02

    def test_grad_remat_scan_scaling(self):
        A = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
        x0 = jax.random.normal(jax.random.PRNGKey(1), (128, 128))

        def f(a, x0):
            def loss(w):
                def body(c, _):
                    return jnp.tanh(c @ w), None
                out, _ = jax.lax.scan(jax.checkpoint(body), x0, None, length=6)
                return out.sum()
            return jax.grad(loss)(a)
        s = self._stats(f, A, x0)
        truth = (6 + 6 + 12) * 2 * 128 ** 3   # fwd + recompute + bwd
        assert abs(s.dot_flops - truth) / truth < 0.05

    def test_shape_bytes_parsing(self):
        assert hlo._shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
        assert hlo._shape_bytes("bf16[2,4]") == 16
        assert hlo._shape_bytes("(f32[4], s32[2,2])") == 16 + 16
        assert hlo._shape_bytes("pred[]") == 1

    def test_collective_detection(self):
        txt = '''ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64] parameter(0)
  ROOT %ar = f32[64]{0} all-reduce(%p), replica_groups={}, to_apply=%add
}
'''
        s = hlo.analyze_module(txt)
        assert s.collective_bytes == 256
        assert s.count_by_kind.get("all-reduce") == 1


class TestCompression:
    def test_quantize_roundtrip_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
        from repro.dist.compression import dequantize, quantize_int8
        q, s = quantize_int8(x)
        err = np.abs(np.asarray(dequantize(q, s) - x))
        assert err.max() <= float(s) / 2 + 1e-6

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 100))
    def test_error_feedback_converges(self, seed):
        """Accumulated EF-compressed sums converge to the true sum: the
        residual stays bounded while the signal accumulates."""
        from repro.dist.compression import quantize_int8
        rng = np.random.default_rng(seed)
        x = rng.normal(size=64).astype(np.float32)
        err = np.zeros_like(x)
        acc = np.zeros_like(x)
        for t in range(64):
            y = x + err
            scale = max(np.abs(y).max(), 1e-12) / 127.0
            q = np.clip(np.round(y / scale), -127, 127)
            sent = q * scale
            err = y - sent
            acc += sent
        # mean of sent == x up to residual/T
        np.testing.assert_allclose(acc / 64, x, atol=np.abs(x).max() / 50 + 1e-3)


class TestMoERouting:
    def test_topk_and_renormalization(self):
        cfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=16)
        logits = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
        gates, ids, aux = moe_mod.route(logits, cfg)
        assert gates.shape == (32, 2) and ids.shape == (32, 2)
        np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-5)
        assert float(aux) > 0.5  # E * sum f*p >= 1 at uniform

    def test_capacity_and_slots(self):
        ids = jnp.array([[0], [0], [0], [1]])
        slots, keep = moe_mod.assign_slots(ids, num_experts=2, cap=2)
        assert keep.tolist() == [[True], [True], [False], [True]]
        assert slots[0, 0] == 0 and slots[1, 0] == 1

    def test_moe_ffn_identity_when_experts_equal(self):
        """If all experts share weights, routing must not matter."""
        cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=8,
                        capacity_factor=4.0)
        d = 16
        key = jax.random.PRNGKey(0)
        wi = jax.random.normal(key, (1, d, 8)) * 0.3
        wg = jax.random.normal(jax.random.fold_in(key, 1), (1, d, 8)) * 0.3
        wo = jax.random.normal(jax.random.fold_in(key, 2), (1, 8, d)) * 0.3
        p = {
            "router": jax.random.normal(jax.random.fold_in(key, 3), (d, 4)),
            "we_i": jnp.tile(wi, (4, 1, 1)),
            "we_g": jnp.tile(wg, (4, 1, 1)),
            "we_o": jnp.tile(wo, (4, 1, 1)),
        }
        x = jax.random.normal(jax.random.fold_in(key, 4), (2, 8, d))
        y, _ = moe_mod.moe_ffn(x, p, cfg)
        # reference: plain gated mlp with the shared expert weights
        h = jnp.einsum("bsd,df->bsf", x, wi[0])
        g = jnp.einsum("bsd,df->bsf", x, wg[0])
        ref = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h, wo[0])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
