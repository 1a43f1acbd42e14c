"""Differentiable harnesses around the Pallas spectral kernels, plus the
non-Pallas accelerated paths, consumed by the kernel registry (api.py).

`make_deltaw_harness(fwd, bwd, bm, bn)` packages the custom-VJP + padding
plumbing once — n padded to the 128-lane boundary (entries padded directly;
padded columns carry c = 0 so they contribute nothing), output sliced back to
(d1, d2), cotangents zero-padded to the backward kernel's block grid, and
the (L, n) coefficient stack handed to the kernels whole (a single (n,)
vector is a stack of one) — and is instantiated for both the FourierFT
kernels (fourier_deltaw.py) and the DCT kernels (dct_deltaw.py).

`circulant_apply_fft` is the circulant adapter's fast apply: x @ C is a
circular convolution, computed as irfft(rfft(x) ⊛ rfft(g)) in O(M log M)
instead of materializing the (d1, d2) gather — an XLA FFT, not a hand-written
Pallas kernel, registered under the accelerated backends by the method
(core/adapter.py).

`fourier_deltaw` remains the standalone entry for benchmarks/tests; it
dispatches through the registry like the adapter stack does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import dct_deltaw as _dk
from repro.kernels import fourier_deltaw as _fk

# Largest dim whose integer phase product stays exact in int32 INCLUDING the
# kernels' row padding to the bm=256 block grid (j runs over padded rows):
#   fourier: j·u       with j ≤ d1p−1, u ≤ d1−1  → d ≤ 46336 (= 181·256)
#   dct:     (2j+1)·u  reduced mod 4d            → d ≤ 32500
# (The pre-registry code used 46340 = ⌊√2³¹⌋, which overflows for
# d ∈ [46337, 46340] once block padding pushes j past d — tightened here.)
FOURIER_INT32_SAFE_DIM = 46336
DCT_INT32_SAFE_DIM = 32500


def _pad_entries(entries: jax.Array) -> jax.Array:
    """Pad (2, n) int32 entries to the 128-lane boundary (zero entries)."""
    n = entries.shape[1]
    npad = -(-n // 128) * 128
    if npad == n:
        return entries
    return jnp.pad(entries, ((0, 0), (0, npad - n)))


def _over_stack(kernel, stack: int):
    """Run `kernel(stacked, *shared)` — stacked leading (L, ...) operand in,
    (L, ...) result out — so it can sit inside a sharded program. A Mosaic
    kernel cannot be partitioned automatically, so under a mesh (set with
    `jax.set_mesh` / `jax.sharding.use_abstract_mesh` by the launch layer)
    it runs in a `shard_map` that splits the layer stack over the mesh
    axes when their size divides L, and replicates it otherwise. Without a
    mesh the kernel is called as is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel
    axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    split = P(axes) if stack % math.prod(mesh.shape[a] for a in axes) == 0 \
        else P()
    return jax.shard_map(kernel, mesh=mesh, in_specs=(split, P(), P()),
                         out_specs=split, check_vma=False)


def make_deltaw_harness(fwd_kernel, bwd_kernel, bm: int, bn: int):
    """Reusable custom-VJP + padding wrapper for (c, entries) -> ΔW spectral
    kernels.

    fwd_kernel(c (L,1,npad), u, v (1,npad), d1, d2, alpha, interpret=) ->
    (L, d1p, d2p) tile-padded ΔW stack; bwd_kernel(g (L,d1p,d2p), u, v, d1,
    d2, alpha, interpret=) -> (L, 1, npad) dc. The returned callable is
    `h(c, entries, d1, d2, alpha, *, interpret=False)` accepting c as (n,)
    or stacked (L, n); the stack is a grid axis of the kernels."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
    def _deltaw(c, entries, d1, d2, alpha, interpret):
        return _fwd(c, entries, d1, d2, alpha, interpret)[0]

    def _fwd(c, entries, d1, d2, alpha, interpret):
        ep = _pad_entries(entries)
        n, npad = c.shape[1], ep.shape[1]
        cp = jnp.pad(c, ((0, 0), (0, npad - n)))[:, None, :]
        fwd = lambda cc, u, v: fwd_kernel(cc, u, v, d1, d2, alpha,
                                          interpret=interpret)
        out = _over_stack(fwd, c.shape[0])(cp, ep[:1], ep[1:])
        return out[:, :d1, :d2], (entries,)

    def _bwd(d1, d2, alpha, interpret, res, g):
        (entries,) = res
        n = entries.shape[1]
        ep = _pad_entries(entries)
        d1p, d2p = -(-d1 // bm) * bm, -(-d2 // bn) * bn
        gp = jnp.pad(g.astype(jnp.float32),
                     ((0, 0), (0, d1p - d1), (0, d2p - d2)))
        bwd = lambda gg, u, v: bwd_kernel(gg, u, v, d1, d2, alpha,
                                          interpret=interpret)
        dc = _over_stack(bwd, g.shape[0])(gp, ep[:1], ep[1:])
        return (dc[:, 0, :n], None)

    _deltaw.defvjp(_fwd, _bwd)

    def harness(c: jax.Array, entries: jax.Array, d1: int, d2: int,
                alpha: float, *, interpret: bool = False) -> jax.Array:
        stack = c.astype(jnp.float32).reshape(-1, c.shape[-1])
        out = _deltaw(stack, entries, d1, d2, alpha, interpret)
        return out if c.ndim == 2 else out[0]

    return harness


fourier_deltaw_harness = make_deltaw_harness(
    _fk.deltaw_pallas, _fk.dc_pallas, _fk.DEFAULT_BM, _fk.DEFAULT_BN)
dct_deltaw_harness = make_deltaw_harness(
    _dk.deltaw_pallas, _dk.dc_pallas, _dk.DEFAULT_BM, _dk.DEFAULT_BN)


# ---------------------------------------------------------------------------
# Circulant fast apply
# ---------------------------------------------------------------------------

def circulant_apply_fft(x: jax.Array, kernel: jax.Array, d1: int, d2: int,
                        alpha: float) -> jax.Array:
    """y = x @ ΔW for ΔW[j,k] = α/(d1·d2)·g[(k−j) mod M], M = max(d1, d2),
    without materializing ΔW: zero-pad x to M, circularly convolve with g via
    rfft/irfft (O(M log M) per token vs O(d1·d2)), truncate to d2 columns.

    x (..., d1); kernel (..., M) broadcast-aligned against x's batch dims
    ((M,) per layer on the factored path, (B, 1, M) per-row on the bank
    path). Exactly zero for a zero kernel (zero spectrum ⊛ anything = 0),
    preserving the adapter bank's reserved-zero-row contract."""
    m = kernel.shape[-1]
    xf = x.astype(jnp.float32)
    if m != d1:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 1) + [(0, m - d1)])
    spec = jnp.fft.rfft(xf, axis=-1) \
        * jnp.fft.rfft(kernel.astype(jnp.float32), axis=-1)
    y = jnp.fft.irfft(spec, n=m, axis=-1)[..., :d2]
    return y * (alpha / (d1 * d2))


# ---------------------------------------------------------------------------
# Standalone FourierFT entry (benchmarks / tests) — registry-dispatched
# ---------------------------------------------------------------------------

def fourier_deltaw(c: jax.Array, entries: jax.Array, d1: int, d2: int,
                   alpha: float, *, backend: str = "auto",
                   out_dtype=None) -> jax.Array:
    """ΔW for c (n,) -> (d1, d2), or stacked c (L, n) -> (L, d1, d2).

    `backend`: auto | pallas | interpret | einsum — resolved through the
    kernel registry exactly like `AdapterMethod.site_delta` (api.resolve_op),
    including the int32-bound einsum fallback for vocab-sized grids."""
    from repro.configs.base import PEFTConfig
    from repro.kernels import api
    peft = PEFTConfig(method="fourierft", alpha=alpha, kernel_backend=backend)
    op = api.resolve_op("deltaw", "fourierft", peft, d1, d2)
    out = op({"c": c}, {"entries": entries}, d1, d2, peft)
    return out.astype(out_dtype) if out_dtype is not None else out
