"""Pallas TPU kernels for the DCT (LoCA-style, arXiv:2502.06820) ΔW and its
VJP — the cosine-only sibling of fourier_deltaw.py, reusing its integer
phase-block trick with half-integer row phases:

    ΔW[j,k] = α/(d1·d2) · Σ_l c_l · cos(π(2j+1)u_l/2d1) · cos(π(2k+1)v_l/2d2)
            = (C1 ⊙ c) @ C2ᵀ          (one MXU matmul per tile — no sin term)

Phase precision: cos(π(2j+1)u/2d) has period 4d in the integer product
(2j+1)·u, which is reduced exactly in int32 — (2j+1)·u < 2³¹ holds for every
row of the block-padded grid when d ≤ ops.DCT_INT32_SAFE_DIM (≈32.5k; the
bound includes the up-to-(bm−1)-row padding, unlike a naive d² estimate).
Vocab-sized grids route to the einsum reference via the op's `max_dim`.

Backward (`dc`): same tiling over the cotangent g; per tile
    dc += Σ_k (gᵀ C1)[k,:] ⊙ C2[k,:]
accumulated into the layer's (1, n) block across its sequential tile
steps. Grid, block layout and matmul precision as in fourier_deltaw.py: a
leading axis over the (L, n) layer stack.

VMEM at (bm, bn, n) = (256, 256, 1024): basis blocks 2·256·1024·4B = 2 MB +
0.25 MB tile accumulator — half the FourierFT kernel's footprint (no sin
blocks), comfortably double-bufferable in 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fourier_deltaw as _fk

PI = 3.141592653589793

DEFAULT_BM = 256
DEFAULT_BN = 256

# Capability metadata for the repro.analysis kernel verifier (DESIGN.md
# §Analysis). phase "half": the row phase product is (2j+1)·u reduced mod 4d
# (max j = ceil(d/bm)·bm − 1, u < d), giving a derived int32-safe bound of
# 32768 — ops.DCT_INT32_SAFE_DIM (32500) declares tighter, which is fine;
# the verifier only fails bounds LOOSER than derived.
CAPS = {
    "kind": "deltaw_phase",
    "phase": "half",
    "bm": DEFAULT_BM,
    "bn": DEFAULT_BN,
    "trig_terms": 1,
    "n_ref": 1024,
}


def _cos_block(idx0: jax.Array, size: int, dim: int, uv: jax.Array,
               c: jax.Array | None):
    """Half-integer-phase cosine block for rows [idx0, idx0+size) of a
    `dim`-point DCT axis: cos(π(2j+1)u/2d), optionally pre-scaled by c.
    uv and c are (1, n) rows."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0) + idx0
    prod = (2 * rows + 1) * uv                              # exact in int32
    prod = jax.lax.rem(prod, jnp.int32(4 * dim))            # cos period: 4d
    cos = jnp.cos(prod.astype(jnp.float32) * (PI / (2.0 * dim)))
    if c is not None:
        cos = cos * c
    return cos


def _deltaw_kernel(c_ref, u_ref, v_ref, o_ref, *, d1, d2, alpha, bm, bn):
    i = pl.program_id(1)
    j = pl.program_id(2)
    cb = _cos_block(i * bm, bm, d1, u_ref[...], c_ref[...])
    rb = _cos_block(j * bn, bn, d2, v_ref[...], None)
    acc = _fk._dot(cb, rb, ((1,), (1,)))
    o_ref[...] = acc * (alpha / (d1 * d2))


def deltaw_pallas(c: jax.Array, u: jax.Array, v: jax.Array, d1: int, d2: int,
                  alpha: float, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                  interpret: bool = False) -> jax.Array:
    """c (L, 1, npad) f32, u/v (1, npad) i32 (npad a multiple of 128; padded
    columns carry c = 0). Returns the ΔW stack (L, d1p, d2p) f32 with
    d1p/d2p the block-padded dims."""
    L, _, npad = c.shape
    d1p = -(-d1 // bm) * bm
    d2p = -(-d2 // bn) * bn
    coef, entry, tile = _fk._specs(npad, bm, bn)
    kernel = functools.partial(_deltaw_kernel, d1=d1, d2=d2, alpha=alpha,
                               bm=bm, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=(L, d1p // bm, d2p // bn),
        in_specs=[coef, entry, entry],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, d1p, d2p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="dct_deltaw_fwd",
    )(c, u, v)


def _dc_kernel(g_ref, u_ref, v_ref, o_ref, *, d1, d2, alpha, bm, bn):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[...].astype(jnp.float32)                    # (bm, bn)
    cb = _cos_block(i * bm, bm, d1, u_ref[...], None)
    rb = _cos_block(j * bn, bn, d2, v_ref[...], None)
    a = _fk._dot(g, cb, ((0,), (0,)))                     # (bn, n)
    o_ref[...] += jnp.sum(a * rb, axis=0, keepdims=True) * (alpha / (d1 * d2))


def dc_pallas(g: jax.Array, u: jax.Array, v: jax.Array, d1: int, d2: int,
              alpha: float, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
              interpret: bool = False) -> jax.Array:
    """g (L, d1p, d2p) f32 cotangent (zero-padded outside (d1, d2)) -> dc
    (L, 1, npad), accumulated per layer over that layer's sequential tiles."""
    L, d1p, d2p = g.shape
    npad = u.shape[-1]
    coef, entry, tile = _fk._specs(npad, bm, bn)
    kernel = functools.partial(_dc_kernel, d1=d1, d2=d2, alpha=alpha,
                               bm=bm, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=(L, d1p // bm, d2p // bn),
        in_specs=[tile, entry, entry],
        out_specs=coef,
        out_shape=jax.ShapeDtypeStruct((L, 1, npad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="dct_deltaw_coef_grad",
    )(g, u, v)
