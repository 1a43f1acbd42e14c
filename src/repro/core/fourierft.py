"""FourierFT core (the paper's contribution, TPU-adapted).

ΔW = α · Re(IFFT2(ToDense(E, c)))  (paper Eq. 2–4, Algorithm 1 normalization)

On TPU we never run an FFT. The closed form

    ΔW[j,k] = α/(d1·d2) · Σ_l c_l · cos(2π(j·u_l/d1 + k·v_l/d2))
            = [cosθ ⊙ c] @ cosφᵀ − [sinθ ⊙ c] @ sinφᵀ

expresses FourierFT as a rank-2n adapter with frozen Fourier factors and a
trainable diagonal — two MXU matmuls (see DESIGN.md §2). The FFT form survives
as the reference oracle in `repro.kernels.ref`.

Entry sampling supports the paper's Eq. 5 Gaussian band-pass frequency bias.
Entries are shared across all layers (paper: one seed for every layer; we use
one seed per adapted weight *shape*, since distinct (d1,d2) grids cannot share
integer entries — GQA value projections are rectangular).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

TWO_PI = 2.0 * np.pi


# ---------------------------------------------------------------------------
# Entry sampling (host-side, deterministic; runs once at adapter init)
# ---------------------------------------------------------------------------

def _bandpass_prob(d1: int, d2: int, fc: float, bandwidth: float,
                   centered: bool = True) -> np.ndarray:
    """Paper Eq. 5: p(u,v) = exp(-((D² - fc²) / (D·W))²), D = distance to the
    matrix center (paper-literal; note that in unshifted DFT indexing the
    center is the Nyquist frequency — pass centered=False for a physical
    wraparound distance-to-DC, i.e. a true low/band-pass over |frequency|).
    D=0 is a removable singularity: p→1 iff fc==0 else p→0."""
    if centered:
        u = np.arange(d1, dtype=np.float64)[:, None] - d1 / 2.0
        v = np.arange(d2, dtype=np.float64)[None, :] - d2 / 2.0
    else:
        uu = np.arange(d1, dtype=np.float64)
        vv = np.arange(d2, dtype=np.float64)
        u = np.minimum(uu, d1 - uu)[:, None]
        v = np.minimum(vv, d2 - vv)[None, :]
    D = np.sqrt(u * u + v * v)
    with np.errstate(divide="ignore", invalid="ignore"):
        z = (D * D - fc * fc) / (D * bandwidth)
    p = np.exp(-np.square(z))
    p[D == 0] = 1.0 if fc == 0 else 0.0
    return p


def sample_entries(d1: int, d2: int, n: int, seed: int = 2024, *,
                   freq_bias: bool = False, fc: float = 0.0,
                   bandwidth: float = 200.0,
                   centered: bool = True) -> jnp.ndarray:
    """Sample n distinct spectral entries of a d1×d2 grid. Returns int32 (2, n).

    No-bias default matches Algorithm 1 (`randperm(d1*d2)[:n]`), decoded
    row-major (`divmod(idx, d2)` — Algorithm 1's `// d1` assumes square W).
    With freq_bias, Gumbel-top-k over Eq. 5 log-probabilities gives an exact
    without-replacement draw from the band-pass distribution.
    """
    if n > d1 * d2:
        raise ValueError(f"n={n} exceeds grid size {d1}x{d2}")
    rng = np.random.default_rng(seed)
    if freq_bias:
        logp = np.log(_bandpass_prob(d1, d2, fc, bandwidth, centered)
                      + 1e-30).ravel()
        gumbel = rng.gumbel(size=logp.shape)
        flat = np.argpartition(-(logp + gumbel), n - 1)[:n]
    elif d1 * d2 <= (1 << 24):
        flat = rng.permutation(d1 * d2)[:n]
    else:
        # huge grids (e.g. embedding-sized): draw-and-dedup, O(n) memory
        flat = np.unique(rng.integers(0, d1 * d2, size=2 * n))
        while flat.size < n:
            flat = np.unique(np.concatenate(
                [flat, rng.integers(0, d1 * d2, size=2 * n)]))
        flat = rng.permutation(flat)[:n]
    uv = np.stack(np.divmod(flat.astype(np.int64), d2))
    return jnp.asarray(uv, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Fourier bases (traced; generated on the fly, never checkpointed)
# ---------------------------------------------------------------------------

def phase_products(d: int, e: jax.Array, mul: int = 1, add: int = 0,
                   period: int = 0) -> jax.Array:
    """(mul·j + add)·e_l for rows j in [0, d), as float32 (d, n), reduced
    mod `period` (default d) in int32 while the product cannot overflow —
    the same exact phases the Pallas kernels compute, so cos/sin see
    arguments of one period at full float32 precision. Vocab-sized grids
    past the int32 bound keep the unreduced float product."""
    period = period or d
    rows = mul * jnp.arange(d, dtype=jnp.int32)[:, None] + add
    if (mul * (d - 1) + add) * (d - 1) < 2 ** 31:
        return ((rows * e[None, :].astype(jnp.int32)) % period).astype(
            jnp.float32)
    return rows.astype(jnp.float32) * e[None, :].astype(jnp.float32)


def fourier_angles(entries: jax.Array, d1: int, d2: int):
    """Phase grids for the selected entries: θ[j,l] = 2π·j·u_l/d1 (d1, n)
    and φ[k,l] = 2π·k·v_l/d2 (d2, n)."""
    theta = (TWO_PI / d1) * phase_products(d1, entries[0])
    phi = (TWO_PI / d2) * phase_products(d2, entries[1])
    return theta, phi


def fourier_bases(entries: jax.Array, d1: int, d2: int):
    theta, phi = fourier_angles(entries, d1, d2)
    return jnp.cos(theta), jnp.sin(theta), jnp.cos(phi), jnp.sin(phi)


# ---------------------------------------------------------------------------
# ΔW materialization (einsum path; the Pallas path lives in repro.kernels)
# ---------------------------------------------------------------------------

def materialize_delta(c: jax.Array, entries: jax.Array, d1: int, d2: int,
                      alpha: float, *, out_dtype=None) -> jax.Array:
    """ΔW for one layer (c: (n,)) or a stack (c: (L, n) -> (L, d1, d2)).

    scale = α/(d1·d2) matches `torch.fft.ifft2` backward normalization used by
    the paper's Algorithm 1.
    """
    cos_t, sin_t, cos_p, sin_p = fourier_bases(entries, d1, d2)
    scale = alpha / (d1 * d2)
    c = c.astype(jnp.float32)
    if c.ndim == 1:
        dw = (cos_t * c) @ cos_p.T - (sin_t * c) @ sin_p.T
    else:
        # stacked layers: contract n against shared bases
        dw = (jnp.einsum("ln,dn,en->lde", c, cos_t, cos_p)
              - jnp.einsum("ln,dn,en->lde", c, sin_t, sin_p))
    dw = dw * scale
    return dw.astype(out_dtype) if out_dtype is not None else dw


def factored_apply(x: jax.Array, c: jax.Array, entries: jax.Array,
                   d1: int, d2: int, alpha: float) -> jax.Array:
    """y += x @ ΔW without materializing ΔW (rank-2n bypass).

    x: (..., d1) -> (..., d2). Exactly equals x @ materialize_delta(...).
    """
    cos_t, sin_t, cos_p, sin_p = fourier_bases(entries, d1, d2)
    scale = alpha / (d1 * d2)
    xf = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    pc = (xf @ cos_t) * c                      # (..., n)
    ps = (xf @ sin_t) * c
    y = pc @ cos_p.T - ps @ sin_p.T
    return (y * scale).astype(x.dtype)


def delta_norm(c: jax.Array, entries: jax.Array, d1: int, d2: int,
               alpha: float) -> jax.Array:
    """||ΔW||_F via Parseval, without materialization (logging/guards).

    ⟨cos ψ_l, cos ψ_m⟩ over the grid is (d1·d2/2)·(eq[l,m] + conj[l,m]) where
    conj matches entry m against (-u_l, -v_l) mod (d1, d2) — conjugate-pair
    entries share one real basis function, so the Gram matrix is not diagonal;
    the exact O(n²) form is cheap at adapter sizes."""
    u, v = entries[0], entries[1]
    cf = c.astype(jnp.float32)
    conj = ((u[:, None] == (d1 - u[None, :]) % d1)
            & (v[:, None] == (d2 - v[None, :]) % d2))
    s = jnp.sum(jnp.square(cf)) + jnp.einsum(
        "l,m,lm->", cf, cf, conj.astype(jnp.float32))
    scale = alpha / (d1 * d2)
    return scale * jnp.sqrt(jnp.maximum(s, 0.0) * d1 * d2 / 2.0)
