"""Paged decode attention: K/V gathered through a block table (DESIGN.md
§Paging).

The continuous-batching runtime's paged KV cache stores rows in a global
pool of fixed-size pages, `(n_pages, page_size, K, hd)` per layer; each
decode slot maps its logical positions onto pages through a block-table row
`(pages_per_seq,)`. This module provides the decode attention over that
layout as a registry `KernelOp` keyed ``("paged_attention", "attention",
backend)``:

    einsum    — gather the slot's whole logical window with `jnp.take` and
                run the dense ragged-kv_len decode attention
                (`models.attention.direct_attention`). Reference backend and
                the fp32 bit-exactness anchor: the gathered window holds the
                same rows the dense per-slot cache holds, masked columns
                contribute exact zeros, so paged == dense bitwise.
    pallas    — TPU kernel: grid (B, pages_per_seq) with the block table as
                a scalar-prefetch argument, so each grid step DMAs exactly
                ONE page picked by `block_table[b, p]` (the gather happens
                in the index_map — no (B, max_len) window is ever
                materialized in HBM). Online-softmax accumulation across
                the page steps, flash-style.
    interpret — the same kernel under Pallas interpret mode (any platform;
                the CI conformance backend).

`OWNER` is the registry owner shim: `paged_attention` is model-side, not an
adapter-method op, so a module-level object carries the `name` /
`kernel_ops()` surface `kernels.api.ensure_method` collects from.

fn signature (all backends):

    fn(q, k_pages, v_pages, block_table, kv_len) -> out

    q           (B, W, H, dh)   a window of W consecutive query rows per
                                slot (W == 1 for plain decode; W == k+1 for
                                draft verification, DESIGN.md §Speculation)
    k_pages     (P, ps, K, dh)  one layer's page pool (post-RoPE K)
    v_pages     (P, ps, K, dh)
    block_table (B, PPS) int32  per-slot logical-page -> physical-page map
    kv_len      (B,)     int32  valid length seen by query row 0 (its own KV
                                row included); row j attends columns
                                < kv_len + j — causal inside the window,
                                ragged across slots. Dirt rows contribute
                                exact 0. W == 1 reduces to the single-query
                                decode mask (positions >= kv_len masked).
    out         (B, W, H, dh)   in v_pages.dtype
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.api import KernelOp
from repro.models import attention as attn_mod

NEG_INF = attn_mod.NEG_INF
# float32 matmuls in the kernel: Mosaic's default contracts f32 operands in
# one bf16 pass (~4e-3 relative error against the float32 reference)
_F32 = jax.lax.Precision.HIGHEST

# Capability metadata for the repro.analysis kernel verifier (DESIGN.md
# §Analysis): the declared online-softmax scratch layout, checked against
# the canonical derivation (running max/denom are one f32 per (kv_head,
# window-row x group) pair; the accumulator adds the head dim), plus
# reference dims for the VMEM-footprint check. Must match the
# `scratch_shapes` passed to pallas_call below — the verifier exists so
# a retile can't change one without the other.
CAPS = {
    "kind": "paged_attention",
    "scratch": {"m": ("K", "W*G"), "l": ("K", "W*G"),
                "acc": ("K", "W*G", "dh")},
    "ref": {"K": 8, "G": 4, "W": 8, "dh": 128, "ps": 16},
}


# ---------------------------------------------------------------------------
# einsum reference
# ---------------------------------------------------------------------------

def paged_attention_einsum(q, k_pages, v_pages, block_table, kv_len):
    """Gather the logical window through the block table, then run the dense
    ragged decode attention. (B, PPS*ps) window rows at positions >= the
    per-query limit are dirt — masked to exact zeros, so this is
    bit-identical (fp32) to the dense per-slot cache path whenever the valid
    rows hold the same values. q_len == 1 keeps the original single-query
    path; q_len > 1 applies the in-window causal mask (col < kv_len + j)."""
    B, PPS = block_table.shape
    ps = k_pages.shape[1]
    k = jnp.take(k_pages, block_table, axis=0).reshape(
        B, PPS * ps, *k_pages.shape[2:])
    v = jnp.take(v_pages, block_table, axis=0).reshape(
        B, PPS * ps, *v_pages.shape[2:])
    if q.shape[1] == 1:
        return attn_mod.direct_attention(q, k, v, causal=False, kv_len=kv_len)
    return attn_mod.windowed_decode_attention(q, k, v, kv_len)


# ---------------------------------------------------------------------------
# Pallas kernel: one page per grid step, block table as scalar prefetch
# ---------------------------------------------------------------------------

def _paged_attn_kernel(bt_ref, kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, page_size, group):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (K, R, dh), r = w*G + g
    k = k_ref[0]                                   # (ps, K, dh)
    v = v_ref[0]
    R, dh = q.shape[1], q.shape[2]
    qs = q.astype(jnp.float32) * (dh ** -0.5)
    s = jnp.einsum("krd,tkd->krt", qs, k.astype(jnp.float32),
                   precision=_F32)
    cols = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page_size), 2)
    # query row r = w*G + g sits at window position w and sees kv_len + w
    # columns (causal inside the window, ragged across slots; W == 1 is the
    # plain decode mask)
    lim = kvlen_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, (1, R, 1), 1) // group
    valid = cols < lim
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # explicit mask after exp: a fully-masked page must contribute 0, not
    # exp(NEG_INF - NEG_INF) = 1, while m is still at its -inf init
    pexp = jnp.exp(s - m_new[..., None]) * valid.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + pexp.sum(axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[..., None]
                    + jnp.einsum("krt,tkd->krd", pexp,
                                 v.astype(jnp.float32), precision=_F32))
    m_ref[...] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _done():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, block_table, kv_len, *,
                           interpret: bool = False):
    """The window's query rows are regrouped outside the kernel into one
    (K, W*G, dh) block per slot — row w*G + g is window position w, group
    member g of KV head k — so the kernel runs 3-D batched matmuls over
    the KV heads with no in-kernel reshape (Mosaic cannot split a minor
    dim of W*G into (W, G) for W > 1)."""
    B, W, H, dh = q.shape
    _, ps, K, _ = k_pages.shape
    PPS = block_table.shape[1]
    G = H // K
    R = W * G
    qr = q.reshape(B, W, K, G, dh).transpose(0, 2, 1, 3, 4).reshape(
        B, K, R, dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # block_table, kv_len
        grid=(B, PPS),
        in_specs=[
            pl.BlockSpec((1, K, R, dh), lambda b, p, bt, kl: (b, 0, 0, 0)),
            # the gather: each (b, p) grid step pulls the ONE physical page
            # the block table names for slot b's logical page p
            pl.BlockSpec((1, ps, K, dh),
                         lambda b, p, bt, kl: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, K, dh),
                         lambda b, p, bt, kl: (bt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, R, dh),
                               lambda b, p, bt, kl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, R), jnp.float32),        # running max
            pltpu.VMEM((K, R), jnp.float32),        # running denom
            pltpu.VMEM((K, R, dh), jnp.float32),    # running accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=ps, group=G),
        out_shape=jax.ShapeDtypeStruct((B, K, R, dh), v_pages.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention",
    )(block_table, kv_len, qr, k_pages, v_pages)
    return out.reshape(B, K, W, G, dh).transpose(0, 2, 1, 3, 4).reshape(
        B, W, H, dh)


# ---------------------------------------------------------------------------
# Registry owner shim
# ---------------------------------------------------------------------------

class _PagedAttentionOwner:
    """Registry owner for the model-side paged_attention op: carries the
    `name`/`kernel_ops()` surface `api.ensure_method` collects, nothing
    else (no adapter state, no sites)."""
    name = "attention"
    has_site_params = False

    def kernel_ops(self):
        return (
            KernelOp("paged_attention", self.name, "einsum",
                     paged_attention_einsum,
                     note="block-table gather + dense ragged decode attn"),
            KernelOp("paged_attention", self.name, "pallas",
                     functools.partial(paged_attention_pallas,
                                       interpret=False),
                     platforms=("tpu",),
                     note="scalar-prefetch page gather, online softmax",
                     caps=CAPS),
            KernelOp("paged_attention", self.name, "interpret",
                     functools.partial(paged_attention_pallas,
                                       interpret=True),
                     caps=CAPS),
        )


OWNER = _PagedAttentionOwner()
