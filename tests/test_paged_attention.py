"""`paged_attention` kernel-op conformance (DESIGN.md §Paging / §Kernels):
every registered backend against the einsum reference, the einsum backend
against the dense ragged decode attention under an identity block table,
registry capability routing (interpret on any platform, pallas TPU-gated,
auto -> einsum off-TPU), kv_len edge cases, and the MULTI-TOKEN verify
window (DESIGN.md §Speculation): q_len in {1, 2, 5, 9} on every backend
against a per-row single-query loop, with W == 1 bitwise-identical to the
historical single-query semantics; and the grid's stop at each slot's last
live page: ragged lengths around page boundaries, a NaN-poisoned pool past
kv_len, and the host count of the pages the kernel visits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PEFTConfig
from repro.kernels import api as kernel_api
from repro.kernels import paged_attention as pa
from repro.models import attention as attn_mod


def _case(seed, B=3, H=8, K=2, dh=16, n_pages=14, ps=4, pps=6, W=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, W, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, K, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, K, dh)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, size=(B, pps)), jnp.int32)
    # ragged: row j of the window reads kv_len + j rows, so the deepest
    # read (kv_len + W - 1) must stay inside the block-table window
    kv_len = jnp.asarray(rng.integers(1, pps * ps - W + 2, size=(B,)),
                         jnp.int32)
    return q, kp, vp, bt, kv_len


class TestConformance:
    @pytest.mark.parametrize("seed", range(4))
    def test_interpret_matches_einsum(self, seed):
        q, kp, vp, bt, kv_len = _case(seed)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_mha_no_gqa_groups(self):
        """K == H (G = 1) exercises the degenerate group reshape."""
        q, kp, vp, bt, kv_len = _case(7, H=4, K=4)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_kv_len_edges(self):
        """kv_len = 1 (a freshly reset slot) and kv_len = full window."""
        q, kp, vp, bt, _ = _case(11)
        pps, ps = bt.shape[1], kp.shape[1]
        kv_len = jnp.asarray([1, pps * ps, ps], jnp.int32)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        assert not np.isnan(np.asarray(out)).any()

    def test_einsum_equals_dense_ragged_attention(self):
        """Identity block table over a pool that IS the dense cache laid
        out page by page: paged einsum == direct_attention bit-for-bit
        (fp32) — the exactness anchor the runtime acceptance rests on."""
        rng = np.random.default_rng(3)
        B, H, K, dh, ps, pps = 2, 4, 2, 8, 4, 5
        max_len = pps * ps
        ck = jnp.asarray(rng.normal(size=(B, max_len, K, dh)), jnp.float32)
        cv = jnp.asarray(rng.normal(size=(B, max_len, K, dh)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
        kv_len = jnp.asarray([7, 18], jnp.int32)
        # pool: page b*pps + p holds row-block p of batch row b
        kp = ck.reshape(B * pps, ps, K, dh)
        vp = cv.reshape(B * pps, ps, K, dh)
        bt = jnp.arange(B * pps, dtype=jnp.int32).reshape(B, pps)
        ref = attn_mod.direct_attention(q, ck, cv, causal=False,
                                        kv_len=kv_len)
        out = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestWindowedConformance:
    """Multi-token verify window (DESIGN.md §Speculation): query row j
    attends pool positions < kv_len + j. The ground truth is the ALREADY
    PROVEN single-query op run once per row — the windowed op must be the
    batched equivalent of that loop on every backend."""

    @staticmethod
    def _rowwise_reference(q, kp, vp, bt, kv_len):
        W = q.shape[1]
        rows = [pa.paged_attention_einsum(q[:, j:j + 1], kp, vp, bt,
                                          kv_len + j)
                for j in range(W)]
        return jnp.concatenate(rows, axis=1)

    @pytest.mark.parametrize("W", [1, 2, 5, 9])
    def test_einsum_matches_rowwise_single_query(self, W):
        q, kp, vp, bt, kv_len = _case(21 + W, W=W)
        ref = self._rowwise_reference(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("W", [1, 2, 5, 9])
    def test_interpret_matches_einsum(self, W):
        q, kp, vp, bt, kv_len = _case(31 + W, W=W)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_w1_bitwise_identical_to_single_query(self):
        """W == 1 is not merely close to the old semantics — the einsum
        path must be the SAME computation (bitwise), so wiring verify
        through the windowed op cannot perturb plain decode."""
        q, kp, vp, bt, kv_len = _case(17)
        single = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        ref = self._rowwise_reference(q, kp, vp, bt, kv_len)
        np.testing.assert_array_equal(np.asarray(single), np.asarray(ref))

    def test_ragged_kv_len_and_window_edges(self):
        """Extremes: a slot one token past reset (kv_len=1) and a slot
        whose window's deepest row reads the full block-table span."""
        W = 5
        q, kp, vp, bt, _ = _case(41, W=W)
        pps, ps = bt.shape[1], kp.shape[1]
        kv_len = jnp.asarray([1, pps * ps - W + 1, ps], jnp.int32)
        ref = self._rowwise_reference(q, kp, vp, bt, kv_len)
        for fn in (pa.paged_attention_einsum,
                   lambda *a: pa.paged_attention_pallas(*a, interpret=True)):
            out = fn(q, kp, vp, bt, kv_len)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)
            assert not np.isnan(np.asarray(out)).any()

    def test_windowed_mha_no_gqa_groups(self):
        q, kp, vp, bt, kv_len = _case(43, H=4, K=4, W=3)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def _assert_poison_unread(q, kp, vp, bt, kv_len, W, interpret):
    """Point every block-table entry past a slot's last live page at one
    NaN page and check the output is finite and equal to that of a table
    whose entries there name a finite page."""
    B, PPS = bt.shape
    n_pages, ps = kp.shape[:2]
    live = np.arange(B * PPS).reshape(B, PPS)          # distinct live pages
    last = pa.last_live_page(np.asarray(kv_len), W, ps, xp=np)
    past = np.arange(PPS)[None, :] > last[:, None]
    assert past.any()
    poison = n_pages - 1
    assert poison >= B * PPS
    clean = jnp.asarray(np.where(past, 0, live), jnp.int32)
    dirty = jnp.asarray(np.where(past, poison, live), jnp.int32)
    ref = pa.paged_attention_pallas(q, kp, vp, clean, kv_len,
                                    interpret=interpret)
    out = pa.paged_attention_pallas(q, kp.at[poison].set(jnp.nan),
                                    vp.at[poison].set(jnp.nan), dirty,
                                    kv_len, interpret=interpret)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestLastLivePage:
    """The grid fetches and computes pages only up to each slot's last live
    page, (kv_len + W - 2) // ps: the deepest column row W-1 may read."""

    PS, PPS = 16, 4

    def _ragged(self, W, dtype, seed=51):
        q, kp, vp, bt, _ = _case(seed, B=6, n_pages=26, ps=self.PS,
                                 pps=self.PPS, W=W)
        # 1 (a reset slot), a window ending just short of / on / past a page
        # boundary, a mid-page slot and a full slot
        full = self.PPS * self.PS - W + 1
        kv_len = jnp.asarray([1, 14, 16, 17, 40, full], jnp.int32)
        return (q.astype(dtype), kp.astype(dtype), vp.astype(dtype), bt,
                kv_len)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("W", [1, 2, 4])
    def test_ragged_kv_len_matches_einsum(self, W, dtype, tol):
        q, kp, vp, bt, kv_len = self._ragged(W, dtype)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len,
                                        interpret=True)
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("W", [1, 2, 4])
    def test_pages_past_kv_len_are_never_read(self, W):
        """Every block-table entry past a slot's last live page names a
        page no live range names, filled with NaN: a kernel that read or
        computed any of them would return NaN (0 * NaN in the value
        product). The output must equal that of a pool whose entries
        there name finite pages."""
        _assert_poison_unread(*self._ragged(W, jnp.float32), W,
                              interpret=True)

    @pytest.mark.parametrize("kv_len,W,pages", [
        ([1], 1, 1), ([16], 1, 1), ([17], 1, 2), ([14], 4, 2),
        ([13], 4, 1), ([64], 1, 4), ([61], 4, 4), ([500], 1, 4),
        ([1, 16, 17, 64], 1, 1 + 1 + 2 + 4)])
    def test_pages_visited(self, kv_len, W, pages):
        """The host count of the grid steps that fetch and compute: one
        page for a reset slot, a second once a window crosses a page
        boundary, never more than the slot's pages_per_seq."""
        assert pa.pages_visited(np.asarray(kv_len), W, self.PS,
                                self.PPS) == pages


class TestRegistryRouting:
    def test_backends_registered(self):
        assert set(kernel_api.backends_for("paged_attention", pa.OWNER)) \
            == {"pallas", "interpret", "einsum"}

    def test_auto_resolves_einsum_off_tpu(self):
        op = kernel_api.resolve_op("paged_attention", pa.OWNER,
                                   PEFTConfig(), platform="cpu")
        assert op.backend == "einsum"

    def test_auto_resolves_pallas_on_tpu(self):
        op = kernel_api.resolve_op("paged_attention", pa.OWNER,
                                   PEFTConfig(), platform="tpu")
        assert op.backend == "pallas"

    def test_interpret_policy_any_platform(self):
        op = kernel_api.resolve_op(
            "paged_attention", pa.OWNER,
            PEFTConfig(kernel_backend="interpret"), platform="cpu")
        assert op.backend == "interpret"

    def test_resolved_ops_agree(self):
        q, kp, vp, bt, kv_len = _case(5)
        outs = {}
        for backend in ("einsum", "interpret"):
            op = kernel_api.resolve_op("paged_attention", pa.OWNER,
                                       PEFTConfig(kernel_backend=backend))
            outs[backend] = np.asarray(op.fn(q, kp, vp, bt, kv_len))
        np.testing.assert_allclose(outs["interpret"], outs["einsum"],
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas path needs a TPU")
class TestCompiledTPU:
    def test_pallas_matches_einsum(self):
        q, kp, vp, bt, kv_len = _case(0, dh=128, ps=8)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)

    @pytest.mark.parametrize("W", [1, 4])
    def test_pallas_skips_pages_past_kv_len(self, W):
        q, kp, vp, bt, _ = _case(2, B=4, n_pages=40, dh=128, ps=8, pps=8,
                                 W=W)
        kv_len = jnp.asarray([1, 9, 30, 64 - W + 1], jnp.int32)
        _assert_poison_unread(q, kp, vp, bt, kv_len, W, interpret=False)

    @pytest.mark.parametrize("W", [2, 5])
    def test_pallas_windowed_matches_einsum(self, W):
        q, kp, vp, bt, kv_len = _case(1, dh=128, ps=8, W=W)
        ref = pa.paged_attention_einsum(q, kp, vp, bt, kv_len)
        out = pa.paged_attention_pallas(q, kp, vp, bt, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)
