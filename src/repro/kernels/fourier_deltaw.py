"""Pallas TPU kernels for FourierFT ΔW materialization and its VJP.

Forward (`deltaw`): grid over (d1/bm, d2/bn, L) — the output tiles, then the
layer stack innermost. Each tile's cos/sin basis blocks are built *in VMEM*
from integer phase arithmetic (no HBM-resident (d, n) basis — saves
4·(d1+d2)·n·4 bytes of HBM traffic per materialization), once, at the
tile's first layer step, into scratch that the tile's other layer steps
reuse: every layer of a site shares the site's entries, and only the (1, n)
coefficient row changes from layer to layer. Each layer step scales the row
bases by its coefficients and accumulates two MXU matmuls:

    tile = (cosθ ⊙ c) @ cosφᵀ − (sinθ ⊙ c) @ sinφᵀ,  scaled by α/(d1·d2)

So the trig work of a call is that of one layer, whatever the stack depth
L: the reuse factor is the local L (36 for qwen3-4b on one chip, 12 a chip
for yi-9b's 48 layers split over four), and L = 1 does what a per-layer
build does. The per-tile arithmetic is that of a per-layer build, so each
layer's result is bitwise that of a one-layer call.

Phase precision: angles are reduced exactly in int32 — (j·u) mod d1 is exact
while j·u < 2³¹, i.e. for dims ≤ ops.FOURIER_INT32_SAFE_DIM (46336; j runs
over the block-padded rows, hence slightly under ⌊√2³¹⌋) — so cos/sin see
arguments in [0, 2π) with full f32 precision even for 8k×30k weights. The
registry's capability model (api.py `max_dim`) routes larger dims (vocab-sized
grids; not a default adaptation target) to the einsum path.

Backward (`dc`): same grid and the same scratch bases over the incoming
cotangent g; per tile and layer l
    dc[l] += Σ_k cosφ[k,:] ⊙ (gᵀ cosθ)[k,:] − sinφ ⊙ (gᵀ sinθ)
With l innermost a per-layer output block would be written back before its
next visit, so the whole (L, 1, n) dc is one VMEM-resident block, zeroed at
the first grid step; each layer's row still sums its tiles in (i, j) order.
All three axes are sequential ("arbitrary"); v5e has one TensorCore.

Both kernels take the stacked (L, n) coefficients directly: a `jax.vmap`
over `pallas_call` would hand the kernel a squeezed (n,) block of an (L, n)
array, which the TPU's (8, 128) block rule refuses. Matmuls run at float32
precision (`Precision.HIGHEST`).

VMEM at (bm, bn, n) = (256, 256, 1024): single-buffered basis scratch
4·256·1024·4B = 4 MB, double-buffered (bm, bn) tiles 0.5 MB, and the
resident dc block, one buffer of L·n·4B (0.14 MB at L = 36). The TPU
compiler's own count, temporaries included, is 12.7 MB for the forward and
10.8 MB for dc at L = 36 against the 16 MB scoped limit; the scaled row
blocks sit beside the unscaled scratch, so n pads to at most 1280 here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TWO_PI = 6.283185307179586

DEFAULT_BM = 256
DEFAULT_BN = 256

# Machine-checkable capability metadata (repro.analysis kernel verifier,
# DESIGN.md §Analysis): enough to RE-DERIVE the int32 phase bound and the
# VMEM footprint from first principles, so ops.FOURIER_INT32_SAFE_DIM can
# never silently rot when someone retiles the kernel.
#   phase:       "linear" — row phase product is j·u, j over the
#                block-padded grid (max j = ceil(d/bm)·bm − 1), u < d
#   trig_terms:  cos AND sin basis blocks per axis (2·(bm+bn)·n floats)
#   n_ref:       reference spectral count for the VMEM budget check
#   bases:       "scratch" — the basis blocks live in single-buffered VMEM
#                scratch carried over the layer axis
#   l_ref:       reference stack depth for the resident (L, 1, n) dc block
#                (the configs' deepest stack is 81 layers)
CAPS = {
    "kind": "deltaw_phase",
    "phase": "linear",
    "bm": DEFAULT_BM,
    "bn": DEFAULT_BN,
    "trig_terms": 2,
    "n_ref": 1024,
    "bases": "scratch",
    "l_ref": 128,
}


def _phase_block(idx0: jax.Array, size: int, dim: int, uv: jax.Array,
                 c: jax.Array | None):
    """cos/sin basis block for rows [idx0, idx0+size) of a `dim`-point axis.

    uv: (1, n) int32 spectral indices. Returns (cos (size,n), sin (size,n)),
    optionally pre-scaled by c (1, n)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0) + idx0
    prod = rows * uv                                     # exact in int32
    prod = jax.lax.rem(prod, jnp.int32(dim))
    ang = prod.astype(jnp.float32) * (TWO_PI / dim)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if c is not None:
        cos = cos * c
        sin = sin * c
    return cos, sin


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _build_bases(u_ref, v_ref, ct, st, cp, sp, *, d1, d2, bm, bn):
    """At a tile's first layer step, fill the scratch with its unscaled row
    (cosθ, sinθ) and column (cosφ, sinφ) basis blocks."""
    # interpret mode cannot lower program_id inside a pl.when branch
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _build():
        ct[...], st[...] = _phase_block(i * bm, bm, d1, u_ref[...], None)
        cp[...], sp[...] = _phase_block(j * bn, bn, d2, v_ref[...], None)


def _deltaw_kernel(c_ref, u_ref, v_ref, o_ref, ct, st, cp, sp, *, d1, d2,
                   alpha, bm, bn):
    _build_bases(u_ref, v_ref, ct, st, cp, sp, d1=d1, d2=d2, bm=bm, bn=bn)
    c = c_ref[...]
    acc = _dot(ct[...] * c, cp[...], ((1,), (1,))) \
        - _dot(st[...] * c, sp[...], ((1,), (1,)))
    o_ref[...] = acc * (alpha / (d1 * d2))


def _specs(npad: int, bm: int, bn: int):
    """Block specs over a (L, d1p/bm, d2p/bn) grid, the layer axis
    outermost (the DCT kernels' grid): one layer's (1, npad) coefficient
    row and the shared (1, npad) entry rows per step — their last two dims
    equal the arrays', as the TPU (8, 128) block rule asks — and the
    layer's (bm, bn) tile of the (L, d1p, d2p) ΔW stack."""
    coef = pl.BlockSpec((None, 1, npad), lambda l, i, j: (l, 0, 0))
    entry = pl.BlockSpec((1, npad), lambda l, i, j: (0, 0))
    tile = pl.BlockSpec((None, bm, bn), lambda l, i, j: (l, i, j))
    return coef, entry, tile


def _stack_inner_specs(npad: int, bm: int, bn: int):
    """Block specs over the (d1p/bm, d2p/bn, L) grid, the layer axis
    innermost: layer l's (1, npad) coefficient row, the shared (1, npad)
    entry rows — their last two dims equal the arrays', as the TPU (8, 128)
    block rule asks — layer l's (bm, bn) tile (i, j) of an (L, d1p, d2p)
    stack, and the scratch for the four basis blocks, carried over each
    tile's layer steps."""
    coef = pl.BlockSpec((None, 1, npad), lambda i, j, l: (l, 0, 0))
    entry = pl.BlockSpec((1, npad), lambda i, j, l: (0, 0))
    tile = pl.BlockSpec((None, bm, bn), lambda i, j, l: (l, i, j))
    bases = [pltpu.VMEM((bm, npad), jnp.float32),    # cosθ
             pltpu.VMEM((bm, npad), jnp.float32),    # sinθ
             pltpu.VMEM((bn, npad), jnp.float32),    # cosφ
             pltpu.VMEM((bn, npad), jnp.float32)]    # sinφ
    return coef, entry, tile, bases


def deltaw_pallas(c: jax.Array, u: jax.Array, v: jax.Array, d1: int, d2: int,
                  alpha: float, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                  interpret: bool = False) -> jax.Array:
    """c (L, 1, npad) f32, u/v (1, npad) i32 (npad a multiple of 128; padded
    columns carry c = 0). Returns the ΔW stack (L, d1p, d2p) f32 with
    d1p/d2p the block-padded dims."""
    L, _, npad = c.shape
    d1p = -(-d1 // bm) * bm
    d2p = -(-d2 // bn) * bn
    coef, entry, tile, bases = _stack_inner_specs(npad, bm, bn)
    kernel = functools.partial(_deltaw_kernel, d1=d1, d2=d2, alpha=alpha,
                               bm=bm, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=(d1p // bm, d2p // bn, L),
        in_specs=[coef, entry, entry],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, d1p, d2p), jnp.float32),
        scratch_shapes=bases,
        # the bases are built at l == 0 of every (i, j), so the i axis
        # stays safe to split across cores; j and l carry the scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="fourier_deltaw_fwd",
    )(c, u, v)


def _dc_kernel(g_ref, u_ref, v_ref, o_ref, ct, st, cp, sp, *, d1, d2, alpha,
               bm, bn):
    i, j, l = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0) & (l == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _build_bases(u_ref, v_ref, ct, st, cp, sp, d1=d1, d2=d2, bm=bm, bn=bn)
    g = g_ref[...].astype(jnp.float32)                    # (bm, bn)
    a = _dot(g, ct[...], ((0,), (0,)))                    # (bn, n)
    b = _dot(g, st[...], ((0,), (0,)))
    contrib = jnp.sum(a * cp[...] - b * sp[...], axis=0, keepdims=True)
    o_ref[l] += contrib * (alpha / (d1 * d2))


def dc_pallas(g: jax.Array, u: jax.Array, v: jax.Array, d1: int, d2: int,
              alpha: float, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
              interpret: bool = False) -> jax.Array:
    """g (L, d1p, d2p) f32 cotangent (zero-padded outside (d1, d2)) -> dc
    (L, 1, npad), each layer's row accumulated over its tiles in (i, j)
    order into one resident output block."""
    L, d1p, d2p = g.shape
    npad = u.shape[-1]
    _, entry, tile, bases = _stack_inner_specs(npad, bm, bn)
    kernel = functools.partial(_dc_kernel, d1=d1, d2=d2, alpha=alpha,
                               bm=bm, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=(d1p // bm, d2p // bn, L),
        in_specs=[tile, entry, entry],
        out_specs=pl.BlockSpec((L, 1, npad), lambda i, j, l: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, 1, npad), jnp.float32),
        scratch_shapes=bases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="fourier_deltaw_coef_grad",
    )(g, u, v)
