"""Model registry: `build(cfg, peft)` returns a `Model` facade with a uniform
interface across families (dense/moe/audio/vlm transformer, pure-SSM, hybrid).

    model.init(rng)                      -> {"base": ..., "peft": ...}
    model.loss(params, batch)            -> scalar
    model.forward(params, batch)         -> (logits, aux)
    model.decode_step(params, cache, b)  -> (next_tokens, cache)
    model.init_cache(batch, max_len)     -> cache tree
    model.sites                          -> adapter sites (PEFT targets)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, PEFTConfig
from repro.core import adapter as adapter_api
from repro.core import peft as peft_mod
from repro.core.peft import AdapterSite
from repro.kernels import api as kernel_api
from repro.models import mamba2, ssm_lm, transformer, zamba2


def add_time_dim(t: jax.Array) -> jax.Array:
    """Re-add the time dim to per-step tokens: (B,) -> (B, 1); codebook
    tokens (B, CB) -> (B, 1, CB). Shared by Model.prefill and the serve
    engine's decode loop so the two paths cannot diverge."""
    return t[:, None] if t.ndim == 1 else t[:, None, :]


def default_targets(cfg: ModelConfig) -> Tuple[str, ...]:
    """Paper default: attention q/v. Attention-free family: in/out proj."""
    if cfg.family == "ssm":
        return ("wx", "wo_ssm")
    return ("wq", "wv")


def resolve_default_targets(peft: PEFTConfig, cfg: ModelConfig) -> PEFTConfig:
    """Swap the generic ("wq", "wv") default for the family's real targets —
    the ONE place this special case lives (Model build and the serving
    AdapterBank both normalize through it)."""
    if peft.target_modules == ("wq", "wv") and cfg.family == "ssm":
        return peft.replace(target_modules=default_targets(cfg))
    return peft


def adapter_sites(cfg: ModelConfig) -> Tuple[AdapterSite, ...]:
    if cfg.family == "ssm":
        d_inner = cfg.ssm.expand * cfg.d_model
        return (
            AdapterSite("layers/wx", cfg.d_model, d_inner, cfg.num_layers),
            AdapterSite("layers/wo_ssm", d_inner, cfg.d_model, cfg.num_layers),
        )
    if cfg.family == "hybrid":
        d_inner = cfg.ssm.expand * cfg.d_model
        return (
            AdapterSite("shared/wq", cfg.d_model, cfg.attn_dim, zamba2.n_apps(cfg)),
            AdapterSite("shared/wv", cfg.d_model, cfg.kv_dim, zamba2.n_apps(cfg)),
            AdapterSite("layers/wx", cfg.d_model, d_inner, cfg.num_layers),
            AdapterSite("layers/wo_ssm", d_inner, cfg.d_model, cfg.num_layers),
        )
    return (
        AdapterSite("layers/wq", cfg.d_model, cfg.attn_dim, cfg.num_layers),
        AdapterSite("layers/wk", cfg.d_model, cfg.kv_dim, cfg.num_layers),
        AdapterSite("layers/wv", cfg.d_model, cfg.kv_dim, cfg.num_layers),
        AdapterSite("layers/wo", cfg.attn_dim, cfg.d_model, cfg.num_layers),
        AdapterSite("layers/wi", cfg.d_model, cfg.d_ff or cfg.d_model, cfg.num_layers),
    )


_FAMILY_MODULES = {
    "dense": transformer, "moe": transformer, "audio": transformer,
    "vlm": transformer, "ssm": ssm_lm, "hybrid": zamba2,
}


@dataclass
class Model:
    cfg: ModelConfig
    peft: PEFTConfig
    remat: str = "none"
    # serving adapter bank: {method name: PEFTConfig profile} — static config
    # closed over by the jitted graphs; the resident rows themselves travel
    # as params["bank"] arrays (see serve/engine.py AdapterBank)
    bank_profiles: Optional[Dict[str, PEFTConfig]] = None

    def __post_init__(self):
        self._mod = _FAMILY_MODULES[self.cfg.family]
        # resolve the method string exactly once, at model build — unknown
        # names fail here, not deep inside a traced graph
        self.method = adapter_api.resolve(self.peft.method)
        if self.method.has_site_params:
            # resolve per-arch default targets if user kept the generic default
            self.peft = resolve_default_targets(self.peft, self.cfg)
        self.sites = adapter_sites(self.cfg)
        # kernel-backend choice per targeted (site, op), resolved ONCE here
        # (DESIGN.md §Kernels) — an unknown kernel_backend fails at build,
        # and explain_kernels() reports what each hot path will run
        self.kernel_policy = kernel_api.KernelPolicy.build(
            self.method, self.sites, self.peft,
            attention=((self.cfg.n_heads, self.cfg.head_dim)
                       if self.supports_slot_cache else None))

    def _bank_kwargs(self, params: Dict) -> Dict:
        if self.bank_profiles is None:
            return {}
        return {"bank": params.get("bank"),
                "bank_profiles": self.bank_profiles}

    # ---- params -----------------------------------------------------------
    def init(self, rng: jax.Array) -> Dict:
        k1, k2 = jax.random.split(rng)
        base = self._mod.init_params(k1, self.cfg)
        adapters = peft_mod.init_adapters(k2, self.sites, self.peft)
        return {"base": base, "peft": adapters}

    def init_shapes(self) -> Dict:
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # ---- forward/loss -----------------------------------------------------
    def forward(self, params: Dict, batch: Dict):
        return self._mod.forward(params["base"], params["peft"], batch,
                                 self.cfg, self.peft, self.sites,
                                 remat=self.remat,
                                 **self._bank_kwargs(params))

    def loss(self, params: Dict, batch: Dict) -> jax.Array:
        return self._mod.loss_fn(params["base"], params["peft"], batch,
                                 self.cfg, self.peft, self.sites,
                                 remat=self.remat)

    # split-tree loss used by the train step (grads w.r.t. trainable only)
    def loss_from_parts(self, trainable: Dict, frozen_base: Dict,
                        frozen_adapters: Dict, batch: Dict) -> jax.Array:
        adapters = _merge_adapter_trees(trainable.get("peft", {}), frozen_adapters)
        base = frozen_base
        if "head" in trainable:
            base = dict(base)
            base["lm_head"] = trainable["head"]
        return self._mod.loss_fn(base, adapters, batch, self.cfg, self.peft,
                                 self.sites, remat=self.remat)

    # ---- decode -----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   per_slot: bool = False, paged: bool = False,
                   page_size: int = 16,
                   n_pages: Optional[int] = None) -> Dict:
        """paged=True allocates the page-pool cache (DESIGN.md §Paging):
        K/V in (L, n_pages, page_size, ...) pools plus the (B,) per-slot
        position vector — block tables travel per call, managed host-side
        by serve/paging.PagedKVCache (which also picks n_pages)."""
        if paged:
            if n_pages is None:
                raise ValueError("paged cache needs n_pages (the runtime "
                                 "takes it from serve.paging.PagedKVCache)")
            return self._slot_mod().init_paged_cache(self.cfg, batch,
                                                     n_pages, page_size,
                                                     dtype)
        if per_slot:
            return self._slot_mod().init_cache(self.cfg, batch, max_len,
                                               dtype, per_slot=True)
        return self._mod.init_cache(self.cfg, batch, max_len, dtype)

    # ---- per-slot cache (continuous-batching serving, DESIGN §Scheduler) --
    def _slot_mod(self):
        if not self.supports_slot_cache:
            raise NotImplementedError(
                f"family {self.cfg.family!r} ({self.cfg.name}) has no "
                "per-slot cache path — continuous batching currently covers "
                "the token-input transformer families (KV positions are "
                "maskable per slot; recurrent state is not)")
        return self._mod

    @property
    def supports_slot_cache(self) -> bool:
        """True when the family supports the per-slot decode cache: ragged
        per-slot kv_len masking over one fixed-shape KV cache plus the
        write_slot/reset_slots lifecycle (token-input transformer families;
        recurrent families carry un-maskable state, vlm feeds embeds)."""
        return (hasattr(self._mod, "write_slot_cache")
                and self.cfg.embed_inputs and not self.cfg.n_codebooks)

    def write_slot(self, cache: Dict, slot_cache: Dict, slot, length) -> Dict:
        """In-flight prefill: splice a primed batch-1 scratch cache into slot
        row `slot` (position <- `length`) while every other slot keeps
        decoding. `slot`/`length` trace as scalars — one compiled splice per
        scratch length serves all slots."""
        return self._slot_mod().write_slot_cache(cache, slot_cache, slot,
                                                 length)

    def reset_slots(self, cache: Dict, mask) -> Dict:
        """Retire the masked slots of a per-slot cache (positions -> 0)."""
        return self._slot_mod().reset_slots(cache, mask)

    def copy_page(self, cache: Dict, src, dst) -> Dict:
        """COW clone of one physical page of a paged cache (src -> dst)."""
        return self._slot_mod().copy_page(cache, src, dst)

    def prefill_paged(self, params: Dict, cache: Dict, batch: Dict):
        """Shared-prefix tail prefill into a paged cache: compute only the
        unshared tail of the prompt (batch["prefix_len"] tokens are reused
        from resident pages via batch["block_table"]) and splice its KV
        into the slot's pages. Returns (next_tokens, cache)."""
        fn = self._slot_mod().prefill_paged
        return fn(params["base"], params["peft"], cache, batch, self.cfg,
                  self.peft, self.sites,
                  **self._bank_kwargs(params))

    def decode_step(self, params: Dict, cache: Dict, batch: Dict):
        return self._mod.decode_step(params["base"], params["peft"], cache,
                                     batch, self.cfg, self.peft, self.sites,
                                     **self._bank_kwargs(params))

    def verify_step(self, params: Dict, cache: Dict, batch: Dict):
        """Speculative draft verification: one forward over batch["tokens"]
        (B, W) — the last accepted token plus W-1 drafts per slot — writing
        all W KV rows and returning the greedy continuation after each
        (DESIGN.md §Speculation). Cache `pos` is NOT advanced; the
        scheduler commits accepted counts via `advance_pos`."""
        fn = self._slot_mod().verify_step
        return fn(params["base"], params["peft"], cache, batch, self.cfg,
                  self.peft, self.sites,
                  **self._bank_kwargs(params))

    def advance_pos(self, cache: Dict, delta):
        """Per-slot position commit after verification (delta (B,) of
        accepted token counts, or a scalar for drafter rollback)."""
        return self._slot_mod().advance_pos(cache, delta)

    def prefill(self, params: Dict, cache: Dict, batch: Dict):
        """Fill a fresh cache from a whole (B, S[, CB]) prompt in one call.
        Transformer families run a parallel causal forward; recurrent
        families (ssm/hybrid) scan the decode step over the prompt inside
        one jittable graph. Returns (next_tokens, cache)."""
        fn = getattr(self._mod, "prefill", None)
        if fn is not None:
            return fn(params["base"], params["peft"], cache, batch, self.cfg,
                      self.peft, self.sites,
                      **self._bank_kwargs(params))
        tokens = batch["tokens"]
        extra = {k: batch[k] for k in ("adapter_slots",) if k in batch}

        def body(cache, tok):
            nt, cache = self.decode_step(params, cache,
                                         {"tokens": add_time_dim(tok), **extra})
            return cache, nt

        cache, nts = jax.lax.scan(body, cache, jnp.moveaxis(tokens, 1, 0))
        return jax.tree.map(lambda a: a[-1], nts), cache

    # ---- kernels ------------------------------------------------------------
    def explain_kernels(self) -> str:
        """Which kernel backend each targeted (site, op) resolved to —
        the build-time `KernelPolicy` snapshot rendered for humans."""
        return self.kernel_policy.explain()

    # ---- accounting ---------------------------------------------------------
    def trainable_params(self) -> int:
        if self.method.trains_base:
            import numpy as _np
            shapes = jax.eval_shape(
                lambda: self._mod.init_params(jax.random.PRNGKey(0), self.cfg))
            return sum(int(_np.prod(l.shape)) for l in jax.tree.leaves(shapes))
        return peft_mod.count_trainable(self.sites, self.peft)


def _merge_adapter_trees(trainable: Dict, frozen: Dict) -> Dict:
    out = {}
    for name in set(trainable) | set(frozen):
        out[name] = {**frozen.get(name, {}), **trainable.get(name, {})}
    return out


def build(cfg: ModelConfig, peft: Optional[PEFTConfig] = None,
          remat: str = "none") -> Model:
    return Model(cfg, peft or PEFTConfig(), remat=remat)


def analysis_models(methods: Tuple[str, ...] = ("fourierft",),
                    archs: Optional[Tuple[str, ...]] = None):
    """Yield (arch_id, method, Model) for every registered config × method at
    reduced scale — the coverage surface `repro.analysis`'s sharding audit
    walks (`init_shapes()` is eval_shape-cheap; nothing is materialized).
    Unbuildable combinations (a method whose applicability predicate rejects
    the family) are skipped: absent params can't need a sharding rule."""
    import repro.configs as configs
    for arch in (archs or tuple(configs.ARCHS)):
        cfg = configs.reduced(configs.get(arch))
        for m in methods:
            try:
                yield arch, m, build(cfg, PEFTConfig(method=m))
            except (ValueError, NotImplementedError):
                continue
