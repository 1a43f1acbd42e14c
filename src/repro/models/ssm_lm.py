"""Pure-SSM LM (mamba2-2.7b): embedding -> scan of mamba2 blocks -> head.

FourierFT targets the in/out projections (wx / wo_ssm) — the architecture is
attention-free, so the paper's default q/v set is inapplicable; see DESIGN.md
§Arch-applicability.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, PEFTConfig
from repro.models import mamba2
from repro.models.common import cross_entropy, dense_init, rms_norm
from repro.models.transformer import (
    apply_peft_to_layers, make_linear, _remat,
)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "embed": dense_init(k1, (cfg.vocab, cfg.d_model), dtype),
        "layers": mamba2.init_mamba_params(k2, cfg, cfg.num_layers, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "lm_head": dense_init(k3, (cfg.d_model, cfg.vocab), dtype),
    }


def forward(params: Dict, adapters: Dict, batch: Dict, cfg: ModelConfig,
            peft: PEFTConfig, sites, *, remat: str = "none",
            bank=None, bank_profiles=None):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)

    def body(x, lp):
        return mamba2.mamba_block(lp, x, cfg, linear_fn=linear), None

    x, _ = jax.lax.scan(_remat(body, remat), x, eff_layers)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, jnp.float32(0.0)


def loss_fn(params, adapters, batch, cfg, peft, sites, *, remat="none"):
    logits, _ = forward(params, adapters, batch, cfg, peft, sites,
                        remat=remat)
    return cross_entropy(logits, batch["labels"])


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    c = mamba2.init_mamba_cache(cfg, cfg.num_layers, batch, dtype)
    c["pos"] = jnp.zeros((), jnp.int32)
    return c


def decode_step(params: Dict, adapters: Dict, cache: Dict, batch: Dict,
                cfg: ModelConfig, peft: PEFTConfig, sites,
                bank=None, bank_profiles=None):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)    # (B, 1, d)
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)

    # caches in the scan carry (in-place per-layer update; see transformer.py)
    def body(carry, lp_i):
        x, conv_all, ssm_all = carry
        lp, li = lp_i
        c = {"conv": jax.lax.dynamic_index_in_dim(conv_all, li, 0, False),
             "ssm": jax.lax.dynamic_index_in_dim(ssm_all, li, 0, False)}
        x, new_c = mamba2.mamba_decode_step(lp, c, x, cfg, linear_fn=linear)
        conv_all = jax.lax.dynamic_update_index_in_dim(conv_all, new_c["conv"], li, 0)
        ssm_all = jax.lax.dynamic_update_index_in_dim(ssm_all, new_c["ssm"], li, 0)
        return (x, conv_all, ssm_all), None

    (x, conv_c, ssm_c), _ = jax.lax.scan(
        body, (x, cache["conv"], cache["ssm"]),
        (eff_layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return next_tokens, {"conv": conv_c, "ssm": ssm_c, "pos": cache["pos"] + 1}
