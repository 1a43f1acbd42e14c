"""Zamba2-style hybrid: mamba2 blocks with ONE shared transformer block
(attention + MLP) applied before every group of `shared_every` mamba blocks.

Wiring is a nested scan — outer scan over groups (shared block + inner scan
over the group's mamba layers) — so the HLO contains exactly one shared-block
body and one mamba body regardless of depth, with no lax.cond branches
(compile-size- and cost-analysis-exact). A trailing partial group handles
L % shared_every != 0 (zamba2-7b: 81 = 13·6 + 3 ⇒ 14 shared applications).

Beyond-paper (in Zamba2's own spirit): each application owns a FourierFT
coefficient row on the shared q/v projections — the real model specializes
shared blocks with per-application LoRA; we use the paper's technique
(LoRA available via peft.method="lora"). Shared-site adapters are always
factored (materializing W+ΔW per application would defeat weight sharing).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, PEFTConfig
from repro.core import adapter as adapter_api
from repro.models import attention as attn_mod
from repro.models import mamba2
from repro.models.common import apply_rope, cross_entropy, dense_init, rms_norm
from repro.models.transformer import (
    SiteApp, _app_tag, apply_peft_to_layers, make_linear, _remat,
)


def _split(cfg: ModelConfig) -> Tuple[int, int]:
    every = cfg.zamba.shared_every
    return cfg.num_layers // every, cfg.num_layers % every


def n_apps(cfg: ModelConfig) -> int:
    n_full, tail = _split(cfg)
    return n_full + (1 if tail else 0)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    ks = iter(jax.random.split(rng, 12))
    shared = {
        "attn_norm": jnp.ones((d,), dtype),
        "wq": dense_init(next(ks), (d, cfg.attn_dim), dtype),
        "wk": dense_init(next(ks), (d, cfg.kv_dim), dtype),
        "wv": dense_init(next(ks), (d, cfg.kv_dim), dtype),
        "wo": dense_init(next(ks), (cfg.attn_dim, d), dtype),
        "mlp_norm": jnp.ones((d,), dtype),
        "wi": dense_init(next(ks), (d, cfg.d_ff), dtype),
        "wg": dense_init(next(ks), (d, cfg.d_ff), dtype),
        "wo_mlp": dense_init(next(ks), (cfg.d_ff, d), dtype),
    }
    return {
        "embed": dense_init(next(ks), (cfg.vocab, d), dtype),
        "layers": mamba2.init_mamba_params(next(ks), cfg, cfg.num_layers, dtype),
        "shared": shared,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense_init(next(ks), (d, cfg.vocab), dtype),
    }


def _shared_adapter_rows(adapters: Dict, peft: PEFTConfig):
    """-> ({tagged key: stacked rows (napps, ...)}, make_linear apps).

    Shared-site adapters stay factored regardless of method (materializing
    W+ΔW per application would defeat weight sharing) — the trainable leaves
    ride the per-application row dict, frozen aux rides the SiteApp."""
    method = adapter_api.resolve(peft.method)
    tag = _app_tag("ad", method.name)
    trainable = set(method.trainable_leaves(peft))
    rows: Dict[str, jax.Array] = {}
    apps: Dict[str, list] = {}
    for full_name, ad in adapters.items():
        if not full_name.startswith("shared/"):
            continue
        key = full_name.split("/")[-1]
        aux = {}
        for leaf, v in ad.items():
            if leaf in trainable:
                rows[key + tag + leaf] = v
            else:
                aux[leaf] = v
        apps.setdefault(key, []).append(SiteApp(tag, method, aux, peft))
    return rows, apps


def _shared_block(x, shared_params, ad_row, apps, cfg, peft, positions,
                  cache_kv=None, cache_pos=None):
    lp = dict(shared_params)
    lp.update(ad_row)
    linear = make_linear(apps)
    B = x.shape[0]
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = linear(lp, "wq", h).reshape(B, -1, cfg.n_heads, cfg.head_dim)
    k = linear(lp, "wk", h).reshape(B, -1, cfg.n_kv, cfg.head_dim)
    v = linear(lp, "wv", h).reshape(B, -1, cfg.n_kv, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cache_kv is None:
        att = attn_mod.attention(q, k, v, causal=True)
        new_kv = None
    else:
        ck, cv = cache_kv                                  # (B, Smax, K, hd)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, cache_pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, cache_pos, 0, 0))
        att = attn_mod.direct_attention(q, ck, cv, causal=False,
                                        kv_len=cache_pos + 1)
        new_kv = (ck, cv)
    x = x + linear(lp, "wo", att.reshape(B, -1, cfg.attn_dim))
    h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    hi = linear(lp, "wi", h2)
    hg = linear(lp, "wg", h2)
    hi = jax.nn.silu(hg.astype(jnp.float32)).astype(hi.dtype) * hi
    x = x + linear(lp, "wo_mlp", hi)
    return x, new_kv


def _group_views(cfg: ModelConfig, tree):
    """Split stacked (L, ...) leaves into main (n_full, every, ...) and tail
    (tail_len, ...)."""
    n_full, tail_len = _split(cfg)
    every = cfg.zamba.shared_every
    main = jax.tree.map(
        lambda a: a[:n_full * every].reshape((n_full, every) + a.shape[1:]),
        tree)
    tail = jax.tree.map(lambda a: a[n_full * every:], tree) if tail_len else None
    return main, tail


def _row_views(cfg: ModelConfig, rows: Dict):
    n_full, tail_len = _split(cfg)
    main = {k: v[:n_full] for k, v in rows.items()}
    tail = {k: v[n_full] for k, v in rows.items()} if tail_len else None
    return main, tail


def forward(params: Dict, adapters: Dict, batch: Dict, cfg: ModelConfig,
            peft: PEFTConfig, sites, *, remat: str = "none",
            bank=None, bank_profiles=None):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    mamba_adapters = {k: v for k, v in adapters.items()
                      if k.startswith("layers/")}
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], mamba_adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)
    rows, shared_apps = _shared_adapter_rows(adapters, peft)
    main_layers, tail_layers = _group_views(cfg, eff_layers)
    main_rows, tail_rows = _row_views(cfg, rows)

    def mamba_body(x, lp):
        return mamba2.mamba_block(lp, x, cfg, linear_fn=linear), None

    def group_body(x, xs):
        gl, ad_row = xs
        x, _ = _shared_block(x, params["shared"], ad_row, shared_apps, cfg,
                             peft, positions)
        x, _ = jax.lax.scan(mamba_body, x, gl)
        return x, None

    x, _ = jax.lax.scan(_remat(group_body, remat), x, (main_layers, main_rows))
    if tail_layers is not None:
        x, _ = _shared_block(x, params["shared"], tail_rows, shared_apps, cfg,
                             peft, positions)
        x, _ = jax.lax.scan(mamba_body, x, tail_layers)
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
    A = n_apps(cfg)
    c["attn_k"] = jnp.zeros((A, batch, max_len, cfg.n_kv, cfg.head_dim), dtype)
    c["attn_v"] = jnp.zeros((A, batch, max_len, cfg.n_kv, cfg.head_dim), dtype)
    c["pos"] = jnp.zeros((), jnp.int32)
    return c


def decode_step(params: Dict, adapters: Dict, cache: Dict, batch: Dict,
                cfg: ModelConfig, peft: PEFTConfig, sites,
                bank=None, bank_profiles=None):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)    # (B, 1, d)
    B = x.shape[0]
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos.astype(jnp.int32), (B, 1))
    mamba_adapters = {k: v for k, v in adapters.items()
                      if k.startswith("layers/")}
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], mamba_adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)
    rows, shared_apps = _shared_adapter_rows(adapters, peft)
    n_full, tail_len = _split(cfg)

    every = cfg.zamba.shared_every
    main_layers, tail_layers = _group_views(cfg, eff_layers)
    main_rows, tail_rows = _row_views(cfg, rows)

    # every cache stays in the carry, updated in place (see transformer.py)
    def mamba_body(carry, lp_i):
        x, conv_all, ssm_all = carry
        lp, li = lp_i
        c = {"conv": jax.lax.dynamic_index_in_dim(conv_all, li, 0, False),
             "ssm": jax.lax.dynamic_index_in_dim(ssm_all, li, 0, False)}
        x, nc = mamba2.mamba_decode_step(lp, c, x, cfg, linear_fn=linear)
        conv_all = jax.lax.dynamic_update_index_in_dim(conv_all, nc["conv"], li, 0)
        ssm_all = jax.lax.dynamic_update_index_in_dim(ssm_all, nc["ssm"], li, 0)
        return (x, conv_all, ssm_all), None

    def group_body(carry, xs):
        x, conv_all, ssm_all, ck_all, cv_all = carry
        gl, ad_row, gi = xs
        ck = jax.lax.dynamic_index_in_dim(ck_all, gi, 0, False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, gi, 0, False)
        x, (ck, cv) = _shared_block(x, params["shared"], ad_row, shared_apps,
                                    cfg, peft, positions, cache_kv=(ck, cv),
                                    cache_pos=pos)
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, gi, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, gi, 0)
        (x, conv_all, ssm_all), _ = jax.lax.scan(
            mamba_body, (x, conv_all, ssm_all),
            (gl, gi * every + jnp.arange(every, dtype=jnp.int32)))
        return (x, conv_all, ssm_all, ck_all, cv_all), None

    carry = (x, cache["conv"], cache["ssm"], cache["attn_k"], cache["attn_v"])
    carry, _ = jax.lax.scan(
        group_body, carry,
        (main_layers, main_rows, jnp.arange(n_full, dtype=jnp.int32)))
    x, new_conv, new_ssm, new_k, new_v = carry
    if tail_len:
        tk = jax.lax.dynamic_index_in_dim(new_k, n_full, 0, False)
        tv = jax.lax.dynamic_index_in_dim(new_v, n_full, 0, False)
        x, (tk, tv) = _shared_block(x, params["shared"], tail_rows, shared_apps,
                                    cfg, peft, positions, cache_kv=(tk, tv),
                                    cache_pos=pos)
        new_k = jax.lax.dynamic_update_index_in_dim(new_k, tk, n_full, 0)
        new_v = jax.lax.dynamic_update_index_in_dim(new_v, tv, n_full, 0)
        (x, new_conv, new_ssm), _ = jax.lax.scan(
            mamba_body, (x, new_conv, new_ssm),
            (tail_layers, n_full * every + jnp.arange(tail_len, dtype=jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    new_cache = {"conv": new_conv, "ssm": new_ssm, "attn_k": new_k,
                 "attn_v": new_v, "pos": pos + 1}
    return next_tokens, new_cache
