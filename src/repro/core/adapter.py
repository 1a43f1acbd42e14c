"""Pluggable adapter-method API (DESIGN.md §Adapter API).

Every PEFT method is an `AdapterMethod` subclass registered under its config
string (`PEFTConfig.method`). The protocol is the *only* place the codebase
knows what a method stores or computes — core, models, train, serve, and
launch dispatch through `resolve(name)` instead of string-matching, so adding
a spectral variant is one registration here, zero edits elsewhere.

Protocol (per adapted 2-D weight site, stacked over layers on axis 0):

    init_site(rng, site, peft)          -> adapter dict (trainable + frozen)
    trainable_leaves(peft)              -> names of the trainable leaves
    kernel_ops()                        -> KernelOp implementations, keyed
                                           (op, method, backend) — see below
    site_delta(adapter, site, peft)     -> dense ΔW (stack, d1, d2)
    factored_apply(x, tr, aux, d1, d2)  -> y-contribution without ΔW
    bank_apply(x, tr, aux, d1, d2)      -> row-batched factored_apply (serving
                                           adapter bank; tr leaves carry a
                                           leading per-request dim)
    merge_site(eff, key, adapter, ...)  -> fold the site into eff layer tree
    count_trainable(site, peft)         -> |Θ| contribution (paper Table 1)
    shared_storage_numbers(sites, peft) -> frozen numbers a checkpoint must
                                           carry beyond Θ (e.g. 2n entries)

Kernel dispatch (DESIGN.md §Kernels): `site_delta`, `factored_apply`, and
`bank_apply` are implemented ONCE on the base class as registry lookups —
a method contributes math by returning `KernelOp`s from `kernel_ops()`
(an `einsum` reference per op it supports, plus optional `pallas` /
`interpret` accelerated backends with capability constraints). The backend
is chosen per call site by `peft.kernel_backend` + the op's `supports()`
(platform, int32 phase bound, config predicates); `Model` snapshots the
choices once at build as its `kernel_policy`. This is how the FourierFT/DCT
Pallas ΔW kernels and the circulant FFT apply reach the train/serve/merge
hot paths without any method-specific branching outside this file.

Flags: `mergeable` (ΔW folds into W — the zamba2 shared block additionally
keeps any method factored for structural reasons), `linear_delta` (the
contribution is x @ ΔW; BitFit's bias shift is not), `has_site_params`
("none"/"full" own no adapter state), `trains_base` ("full").

Contract required by the serving adapter bank: the factored contribution is
*linear in the trainable leaves* — an all-zero row contributes exactly zero,
which is how heterogeneous-method batches share one jitted graph (every
request gathers a row from every method's bank; non-participating requests
gather the reserved zero row).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import PEFTConfig
from repro.core import basis as basis_mod
from repro.core import fourierft, lora
from repro.kernels import api as kernel_api
from repro.kernels.api import KernelOp


@dataclass(frozen=True)
class AdapterSite:
    name: str          # matches the stacked weight key in base params
    d_in: int
    d_out: int
    stack: int         # number of layers stacked on axis 0 (scan-over-layers)


def _per_row(v: jax.Array, x_ndim: int) -> jax.Array:
    """Align a per-request leaf (B, k...) against x (B, ..., d): insert
    broadcast axes so row b of the leaf meets row b of x (activations inside
    the layer may be (B, d) or (B, T, d) depending on the family)."""
    return v.reshape(v.shape[:1] + (1,) * (x_ndim - v.ndim) + v.shape[1:])


def entry_seed_for(peft: PEFTConfig, site: AdapterSite) -> int:
    """Paper: one shared seed (2024) for all layers. Distinct (d1, d2) grids
    cannot share integer entries, so the seed is offset per site shape only
    when shapes differ; equal-shaped sites share entries exactly as the paper
    prescribes."""
    return peft.entry_seed + hash((site.d_in, site.d_out)) % 1000


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------

class AdapterMethod:
    """Base class: one instance per method, registered by `name`."""

    name: str = ""
    mergeable: bool = True        # ΔW can be folded into the base weight
    linear_delta: bool = True     # contribution is x @ ΔW (BitFit: bias)
    has_site_params: bool = True  # owns per-site adapter state
    trains_base: bool = False     # "full": the base weights are the trainables

    # ---- state ------------------------------------------------------------
    def init_site(self, rng: jax.Array, site: AdapterSite,
                  peft: PEFTConfig) -> Dict:
        raise ValueError(f"no per-site params for method {self.name!r}")

    def trainable_leaves(self, peft: PEFTConfig) -> Tuple[str, ...]:
        return ()

    def split_adapter(self, adapter: Dict,
                      peft: PEFTConfig) -> Tuple[Dict, Dict]:
        """-> (trainable, aux) views of one site's adapter dict."""
        names = set(self.trainable_leaves(peft))
        tr = {k: v for k, v in adapter.items() if k in names}
        aux = {k: v for k, v in adapter.items() if k not in names}
        return tr, aux

    # ---- kernels ----------------------------------------------------------
    def kernel_ops(self) -> Tuple[KernelOp, ...]:
        """KernelOp implementations this method provides, collected lazily
        into the kernel registry on first dispatch (kernels/api.py). Every op
        the method serves needs at least an `einsum` reference; accelerated
        backends (`pallas`/`interpret`) are optional and constraint-gated.
        Implementations must be linear in the trainable leaves (bank
        contract) and return float32."""
        return ()

    def _kernel(self, op: str, peft: PEFTConfig, d1: int,
                d2: int) -> Optional[KernelOp]:
        return kernel_api.resolve_op(op, self, peft, d1, d2, missing_ok=True)

    # ---- math (registry-dispatched; see module docstring) ------------------
    def site_delta(self, adapter: Dict, site: AdapterSite, peft: PEFTConfig,
                   out_dtype=None) -> jax.Array:
        op = self._kernel("deltaw", peft, site.d_in, site.d_out)
        if op is None:
            raise NotImplementedError(f"{self.name} has no dense ΔW form")
        tr, aux = self.split_adapter(adapter, peft)
        dw = op(tr, aux, site.d_in, site.d_out, peft)
        return dw.astype(out_dtype) if out_dtype is not None else dw

    def factored_apply(self, x: jax.Array, trainable: Dict, aux: Dict,
                       d1: int, d2: int, peft: PEFTConfig) -> jax.Array:
        """Additive output contribution for one layer slice, x (..., d1) ->
        (..., d2), in float32. Must equal x @ site_delta(...) exactly (up to
        float error) whenever `linear_delta`."""
        op = self._kernel("factored_apply", peft, d1, d2)
        if op is None:
            raise NotImplementedError(self.name)
        return op(x, trainable, aux, d1, d2, peft)

    def bank_apply(self, x: jax.Array, trainable: Dict, aux: Dict,
                   d1: int, d2: int, peft: PEFTConfig) -> jax.Array:
        """Row-batched factored apply: x (B, ..., d1); every trainable leaf
        carries a leading (B,) per-request dim. Falls back to vmapping the
        per-row path for methods that register no bank op."""
        op = self._kernel("bank_apply", peft, d1, d2)
        if op is not None:
            return op(x, trainable, aux, d1, d2, peft)
        return jax.vmap(
            lambda xr, tr: self.factored_apply(xr, tr, aux, d1, d2, peft)
        )(x, trainable)

    def merge_site(self, eff: Dict, key: str, adapter: Dict,
                   site: AdapterSite, peft: PEFTConfig) -> None:
        """Fold one site into the (stacked) layer tree `eff` in place."""
        eff[key] = eff[key] + self.site_delta(adapter, site, peft,
                                              eff[key].dtype)

    # ---- accounting (paper Table 1 / §3.2) --------------------------------
    def count_trainable(self, site: AdapterSite, peft: PEFTConfig) -> int:
        return 0

    def shared_storage_numbers(self, sites: Sequence[AdapterSite],
                               peft: PEFTConfig) -> int:
        """Frozen numbers stored once per checkpoint beyond the trainables
        (regenerable-from-seed state counts 0)."""
        return 0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, AdapterMethod] = {}


def register(method: AdapterMethod) -> AdapterMethod:
    if not method.name:
        raise ValueError("AdapterMethod.name must be set before registration")
    if method.name in _REGISTRY:
        raise ValueError(f"adapter method {method.name!r} already registered")
    _REGISTRY[method.name] = method
    return method


def resolve(name: str) -> AdapterMethod:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown adapter method {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def registered_methods(site_params_only: bool = False) -> Tuple[str, ...]:
    names = sorted(_REGISTRY)
    if site_params_only:
        names = [n for n in names if _REGISTRY[n].has_site_params]
    return tuple(names)


# ---------------------------------------------------------------------------
# FourierFT (the paper) — spectral coefficients on frozen Fourier entries,
# with the Table-6 random/orthogonal basis ablation folded in via peft.basis.
# ---------------------------------------------------------------------------

def _fourier_basis_only(peft: PEFTConfig) -> bool:
    return getattr(peft, "basis", "fourier") == "fourier"


def _fourier_deltaw_einsum(tr, aux, d1, d2, peft):
    if "entries" in aux:
        return fourierft.materialize_delta(tr["c"], aux["entries"], d1, d2,
                                           peft.alpha)
    return basis_mod.materialize_delta_basis(tr["c"], aux["b1"], aux["b2"],
                                             peft.basis, peft.alpha)


def _fourier_deltaw_pallas(tr, aux, d1, d2, peft, *, interpret):
    from repro.kernels import ops as kops
    return kops.fourier_deltaw_harness(tr["c"], aux["entries"], d1, d2,
                                       peft.alpha, interpret=interpret)


def _fourier_factored_einsum(x, tr, aux, d1, d2, peft):
    if "entries" in aux:
        return fourierft.factored_apply(
            x.astype(jnp.float32), tr["c"], aux["entries"], d1, d2,
            peft.alpha)
    scale = basis_mod.basis_scale(peft.basis, d1, d2, peft.alpha)
    proj = (x.astype(jnp.float32) @ aux["b1"]) \
        * tr["c"].astype(jnp.float32)
    return proj @ aux["b2"].T * scale


def _fourier_bank_einsum(x, tr, aux, d1, d2, peft):
    xf = x.astype(jnp.float32)
    c = _per_row(tr["c"].astype(jnp.float32), x.ndim)
    if "entries" in aux:
        cos_t, sin_t, cos_p, sin_p = fourierft.fourier_bases(
            aux["entries"], d1, d2)
        pc = (xf @ cos_t) * c
        ps = (xf @ sin_t) * c
        return (pc @ cos_p.T - ps @ sin_p.T) * (peft.alpha / (d1 * d2))
    scale = basis_mod.basis_scale(peft.basis, d1, d2, peft.alpha)
    return ((xf @ aux["b1"]) * c) @ aux["b2"].T * scale


class FourierFT(AdapterMethod):
    name = "fourierft"

    def init_site(self, rng, site, peft):
        dtype = jnp.dtype(peft.param_dtype)
        if peft.basis == "fourier":
            entries = fourierft.sample_entries(
                site.d_in, site.d_out, peft.n, entry_seed_for(peft, site),
                freq_bias=peft.freq_bias, fc=peft.fc, bandwidth=peft.bandwidth)
            aux = {"entries": entries}
        else:
            b1, b2 = basis_mod.make_basis(
                jax.random.fold_in(jax.random.PRNGKey(peft.entry_seed),
                                   site.d_in * 131071 + site.d_out),
                peft.basis, site.d_in, site.d_out, peft.n)
            aux = {"b1": b1, "b2": b2}
        c = jax.random.normal(rng, (site.stack, peft.n), dtype)
        return {"c": c, **aux}

    def trainable_leaves(self, peft):
        return ("c",)

    def kernel_ops(self):
        from repro.kernels import fourier_deltaw as fdk
        from repro.kernels import ops as kops
        return (
            KernelOp("deltaw", self.name, "einsum", _fourier_deltaw_einsum),
            KernelOp("deltaw", self.name, "pallas",
                     functools.partial(_fourier_deltaw_pallas,
                                       interpret=False),
                     platforms=("tpu",),
                     max_dim=kops.FOURIER_INT32_SAFE_DIM,
                     requires=_fourier_basis_only,
                     note="integer-phase MXU tiles (fourier_deltaw.py)",
                     caps=fdk.CAPS),
            KernelOp("deltaw", self.name, "interpret",
                     functools.partial(_fourier_deltaw_pallas,
                                       interpret=True),
                     max_dim=kops.FOURIER_INT32_SAFE_DIM,
                     requires=_fourier_basis_only,
                     caps=fdk.CAPS),
            KernelOp("factored_apply", self.name, "einsum",
                     _fourier_factored_einsum),
            KernelOp("bank_apply", self.name, "einsum",
                     _fourier_bank_einsum),
        )

    def count_trainable(self, site, peft):
        return peft.n * site.stack

    def shared_storage_numbers(self, sites, peft):
        if peft.basis != "fourier":
            return 0        # b1/b2 regenerate from entry_seed
        shapes = {(s.d_in, s.d_out) for s in sites}
        return 2 * peft.n * len(shapes)


# ---------------------------------------------------------------------------
# DCT (LoCA-style, arXiv:2502.06820): real cosine basis on frozen entries —
# ΔW[j,k] = α/(d1·d2) Σ_l c_l cos(π(2j+1)u_l/2d1) cos(π(2k+1)v_l/2d2).
# Rank-n factored: ΔW = (C1 ⊙ c) @ C2ᵀ, same wire format as FourierFT
# (one coefficient vector + 2n integer entries per shape group).
# ---------------------------------------------------------------------------

def _dct_bases(entries: jax.Array, d1: int, d2: int):
    # cos(π(2j+1)u/2d) has period 4d in the integer product (2j+1)·u
    c1 = jnp.cos((np.pi / (2.0 * d1)) * fourierft.phase_products(
        d1, entries[0], 2, 1, 4 * d1))
    c2 = jnp.cos((np.pi / (2.0 * d2)) * fourierft.phase_products(
        d2, entries[1], 2, 1, 4 * d2))
    return c1, c2                                              # (d1,n) (d2,n)


def _dct_deltaw_einsum(tr, aux, d1, d2, peft):
    c1, c2 = _dct_bases(aux["entries"], d1, d2)
    c = tr["c"].astype(jnp.float32)
    if c.ndim == 1:
        dw = (c1 * c) @ c2.T
    else:
        dw = jnp.einsum("ln,dn,en->lde", c, c1, c2)
    return dw * (peft.alpha / (d1 * d2))


def _dct_deltaw_pallas(tr, aux, d1, d2, peft, *, interpret):
    from repro.kernels import ops as kops
    return kops.dct_deltaw_harness(tr["c"], aux["entries"], d1, d2,
                                   peft.alpha, interpret=interpret)


def _dct_factored_einsum(x, tr, aux, d1, d2, peft):
    c1, c2 = _dct_bases(aux["entries"], d1, d2)
    proj = (x.astype(jnp.float32) @ c1) * tr["c"].astype(jnp.float32)
    return proj @ c2.T * (peft.alpha / (d1 * d2))


def _dct_bank_einsum(x, tr, aux, d1, d2, peft):
    c1, c2 = _dct_bases(aux["entries"], d1, d2)
    c = _per_row(tr["c"].astype(jnp.float32), x.ndim)
    return ((x.astype(jnp.float32) @ c1) * c) @ c2.T * (peft.alpha / (d1 * d2))


class DCTAdapter(AdapterMethod):
    name = "dct"

    def init_site(self, rng, site, peft):
        entries = fourierft.sample_entries(
            site.d_in, site.d_out, peft.n, entry_seed_for(peft, site),
            freq_bias=peft.freq_bias, fc=peft.fc, bandwidth=peft.bandwidth)
        c = jax.random.normal(rng, (site.stack, peft.n),
                              jnp.dtype(peft.param_dtype))
        return {"c": c, "entries": entries}

    def trainable_leaves(self, peft):
        return ("c",)

    def kernel_ops(self):
        from repro.kernels import dct_deltaw as ddk
        from repro.kernels import ops as kops
        return (
            KernelOp("deltaw", self.name, "einsum", _dct_deltaw_einsum),
            KernelOp("deltaw", self.name, "pallas",
                     functools.partial(_dct_deltaw_pallas, interpret=False),
                     platforms=("tpu",), max_dim=kops.DCT_INT32_SAFE_DIM,
                     note="cosine-only integer-phase tiles (dct_deltaw.py)",
                     caps=ddk.CAPS),
            KernelOp("deltaw", self.name, "interpret",
                     functools.partial(_dct_deltaw_pallas, interpret=True),
                     max_dim=kops.DCT_INT32_SAFE_DIM,
                     caps=ddk.CAPS),
            KernelOp("factored_apply", self.name, "einsum",
                     _dct_factored_einsum),
            KernelOp("bank_apply", self.name, "einsum", _dct_bank_einsum),
        )

    def count_trainable(self, site, peft):
        return peft.n * site.stack

    def shared_storage_numbers(self, sites, peft):
        shapes = {(s.d_in, s.d_out) for s in sites}
        return 2 * peft.n * len(shapes)


# ---------------------------------------------------------------------------
# Circulant (arXiv:2505.00580 family): one kernel g per layer, ΔW[j,k] =
# α/(d1·d2) · g[(k−j) mod M], M = max(d1,d2). max(d1,d2) trainables per site
# per layer. The accelerated apply path is an FFT circular convolution
# (kernels/ops.py circulant_apply_fft, O(M log M) per token) — an XLA FFT
# rather than a hand-written Pallas kernel, registered under the accelerated
# backends; the einsum reference materializes the (d1,d2) gather.
# ---------------------------------------------------------------------------

def _circulant_idx(d1: int, d2: int) -> jnp.ndarray:
    m = max(d1, d2)
    idx = (np.arange(d2)[None, :] - np.arange(d1)[:, None]) % m
    return jnp.asarray(idx, jnp.int32)


def _circ_deltaw_einsum(tr, aux, d1, d2, peft):
    g = tr["kernel"].astype(jnp.float32)
    return jnp.take(g, _circulant_idx(d1, d2), axis=-1) \
        * (peft.alpha / (d1 * d2))


def _circ_factored_einsum(x, tr, aux, d1, d2, peft):
    g = tr["kernel"].astype(jnp.float32)
    dw = jnp.take(g, _circulant_idx(d1, d2), axis=-1) \
        * (peft.alpha / (d1 * d2))
    return x.astype(jnp.float32) @ dw


def _circ_bank_einsum(x, tr, aux, d1, d2, peft):
    g = tr["kernel"].astype(jnp.float32)                 # (B, M)
    dw = jnp.take(g, _circulant_idx(d1, d2), axis=-1) \
        * (peft.alpha / (d1 * d2))
    return jnp.einsum("b...d,bdf->b...f", x.astype(jnp.float32), dw)


def _circ_factored_fft(x, tr, aux, d1, d2, peft):
    from repro.kernels import ops as kops
    return kops.circulant_apply_fft(x, tr["kernel"], d1, d2, peft.alpha)


def _circ_bank_fft(x, tr, aux, d1, d2, peft):
    from repro.kernels import ops as kops
    return kops.circulant_apply_fft(x, _per_row(tr["kernel"], x.ndim),
                                    d1, d2, peft.alpha)


class CirculantAdapter(AdapterMethod):
    name = "circulant"

    def init_site(self, rng, site, peft):
        del rng  # zero-init: fine-tuning starts at the base model (cf. LoRA B)
        m = max(site.d_in, site.d_out)
        return {"kernel": jnp.zeros((site.stack, m),
                                    jnp.dtype(peft.param_dtype))}

    def trainable_leaves(self, peft):
        return ("kernel",)

    def kernel_ops(self):
        # the FFT apply is plain XLA and runs anywhere, but at adapter dims
        # its CPU win over the einsum gather is inside measurement noise —
        # keep the default `auto` chain on the documented semantics
        # (accelerated on TPU, reference elsewhere) by TPU-gating the pallas
        # key; the interpret key stays platform-free so CI cross-checks the
        # FFT math everywhere and CPU users can opt in explicitly.
        fft_note = "XLA rfft circular convolution (not a Pallas kernel)"
        return (
            KernelOp("deltaw", self.name, "einsum", _circ_deltaw_einsum),
            KernelOp("factored_apply", self.name, "einsum",
                     _circ_factored_einsum),
            KernelOp("factored_apply", self.name, "pallas",
                     _circ_factored_fft, platforms=("tpu",), note=fft_note),
            KernelOp("factored_apply", self.name, "interpret",
                     _circ_factored_fft, note=fft_note),
            KernelOp("bank_apply", self.name, "einsum", _circ_bank_einsum),
            KernelOp("bank_apply", self.name, "pallas", _circ_bank_fft,
                     platforms=("tpu",), note=fft_note),
            KernelOp("bank_apply", self.name, "interpret", _circ_bank_fft,
                     note=fft_note),
        )

    def count_trainable(self, site, peft):
        return max(site.d_in, site.d_out) * site.stack


# ---------------------------------------------------------------------------
# LoRA baseline
# ---------------------------------------------------------------------------

def _lora_deltaw_einsum(tr, aux, d1, d2, peft):
    return lora.lora_delta(tr["lora_a"], tr["lora_b"], peft.lora_alpha,
                           peft.lora_r)


def _lora_factored_einsum(x, tr, aux, d1, d2, peft):
    xf = x.astype(jnp.float32)
    y = (xf @ tr["lora_a"].astype(jnp.float32)) \
        @ tr["lora_b"].astype(jnp.float32)
    return y * (peft.lora_alpha / peft.lora_r)


def _lora_bank_einsum(x, tr, aux, d1, d2, peft):
    xf = x.astype(jnp.float32)
    p = jnp.einsum("b...d,bdr->b...r", xf,
                   tr["lora_a"].astype(jnp.float32))
    y = jnp.einsum("b...r,brf->b...f", p,
                   tr["lora_b"].astype(jnp.float32))
    return y * (peft.lora_alpha / peft.lora_r)


class LoRA(AdapterMethod):
    name = "lora"

    def init_site(self, rng, site, peft):
        return lora.init_lora(rng, site.d_in, site.d_out, peft.lora_r,
                              stack=site.stack,
                              dtype=jnp.dtype(peft.param_dtype))

    def trainable_leaves(self, peft):
        return ("lora_a", "lora_b")

    def kernel_ops(self):
        return (
            KernelOp("deltaw", self.name, "einsum", _lora_deltaw_einsum),
            KernelOp("factored_apply", self.name, "einsum",
                     _lora_factored_einsum),
            KernelOp("bank_apply", self.name, "einsum", _lora_bank_einsum),
        )

    def count_trainable(self, site, peft):
        return peft.lora_r * (site.d_in + site.d_out) * site.stack


# ---------------------------------------------------------------------------
# BitFit baseline — a bias shift, not a weight delta (linear_delta=False);
# merging adds to (or creates) the site's `__b` bias leaf. No deltaw op, so
# site_delta raises through the base class's registry miss.
# ---------------------------------------------------------------------------

def _bitfit_factored_einsum(x, tr, aux, d1, d2, peft):
    b = tr["delta_b"].astype(jnp.float32)
    return jnp.broadcast_to(b, x.shape[:-1] + (d2,))


def _bitfit_bank_einsum(x, tr, aux, d1, d2, peft):
    b = tr["delta_b"].astype(jnp.float32)                # (B, d2)
    return jnp.broadcast_to(_per_row(b, x.ndim), x.shape[:-1] + (d2,))


class BitFit(AdapterMethod):
    name = "bitfit"
    linear_delta = False

    def init_site(self, rng, site, peft):
        del rng
        return {"delta_b": jnp.zeros((site.stack, site.d_out),
                                     jnp.dtype(peft.param_dtype))}

    def trainable_leaves(self, peft):
        return ("delta_b",)

    def kernel_ops(self):
        return (
            KernelOp("factored_apply", self.name, "einsum",
                     _bitfit_factored_einsum),
            KernelOp("bank_apply", self.name, "einsum", _bitfit_bank_einsum),
        )

    def merge_site(self, eff, key, adapter, site, peft):
        bkey = key + "__b"
        db = adapter["delta_b"]
        eff[bkey] = (eff[bkey] + db) if bkey in eff else db

    def count_trainable(self, site, peft):
        return site.d_out * site.stack


# ---------------------------------------------------------------------------
# Degenerate methods: no adapter state
# ---------------------------------------------------------------------------

class NoAdapter(AdapterMethod):
    name = "none"
    has_site_params = False


class FullFinetune(AdapterMethod):
    name = "full"
    has_site_params = False
    trains_base = True


register(FourierFT())
register(DCTAdapter())
register(CirculantAdapter())
register(LoRA())
register(BitFit())
register(NoAdapter())
register(FullFinetune())
