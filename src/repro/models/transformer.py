"""Generic decoder-only LM covering the dense / moe / audio / vlm families.

- scan-over-layers with stacked (L, ...) params (compile time independent of
  depth; FourierFT coefficients stack naturally as (L, n)).
- PEFT integration at the linear level: `merged` strategy swaps W for
  W + ΔW before the scan; `factored` threads per-layer adapter slices through
  the scan and applies the method's factored bypass inside each layer. All
  method math is behind the `AdapterMethod` protocol (core/adapter.py) — this
  module never looks at `peft.method` — and every ΔW materialization /
  factored / bank apply the protocol performs dispatches through the kernel
  registry (DESIGN.md §Kernels), so the merged hot path below runs the
  Pallas deltaw kernels on TPU without this module knowing.
- serving adapter bank: per-request resident adapters are gathered ONCE per
  call (outside the layer scan) and applied per slot via `bank_apply` (see
  DESIGN.md §Adapter API).
- decode path updates a stacked KV cache (L, B, Smax, K, hd). With a
  per-slot cache (init_cache(per_slot=True): pos is (B,) instead of a
  scalar) every row decodes at its own position under ragged kv_len
  masking, and write_slot_cache/reset_slots give the continuous-batching
  scheduler its in-flight prefill + slot recycling (DESIGN.md §Scheduler).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, PEFTConfig
from repro.core import adapter as adapter_api
from repro.kernels import api as kernel_api
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.common import (
    apply_rope, cross_entropy, dense_init, rms_norm,
)


# ---------------------------------------------------------------------------
# PEFT-aware linear
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SiteApp:
    """One factored adapter application at a weight key: trainable leaves ride
    the scanned layer tree under `{key}{tag}{leaf}`, frozen aux arrays are
    captured here, and `banked` selects the row-batched `bank_apply` path."""
    tag: str
    method: adapter_api.AdapterMethod
    aux: Dict = field(default_factory=dict)
    peft: PEFTConfig = PEFTConfig()
    banked: bool = False


def make_linear(apps: Dict[str, List[SiteApp]]):
    """Returns linear(lp, name, x): y = x @ lp[name] + bias + adapter apps.

    Each `SiteApp` at `name` reads its trainable per-layer slices out of `lp`
    (the scanned layer tree) and adds `method.factored_apply` — or
    `method.bank_apply` for per-request resident adapters — to y, under the
    app's own PEFTConfig (the global config has no say here)."""

    def linear(lp: Dict, name: str, x: jax.Array) -> jax.Array:
        w = lp[name]
        y = jnp.einsum("...d,df->...f", x, w)
        if name + "__b" in lp:
            y = y + lp[name + "__b"].astype(y.dtype)
        d1, d2 = w.shape
        for app in apps.get(name, ()):
            tr = {leaf: lp[name + app.tag + leaf]
                  for leaf in app.method.trainable_leaves(app.peft)}
            fn = app.method.bank_apply if app.banked \
                else app.method.factored_apply
            y = y + fn(x, tr, app.aux, d1, d2, app.peft).astype(y.dtype)
        return y

    return linear


def _app_tag(kind: str, method_name: str) -> str:
    return f"__{kind}.{method_name}__"


def apply_peft_to_layers(layers: Dict, adapters: Dict, sites, peft: PEFTConfig,
                         prefix: str = "layers/",
                         bank: Optional[Dict] = None,
                         bank_profiles: Optional[Dict[str, PEFTConfig]] = None,
                         bank_slots: Optional[Dict] = None):
    """Returns (eff_layers, apps). merged (and method.mergeable): the method
    folds the site into the stacked tree (W <- W + ΔW; BitFit into the bias).
    factored: trainable leaves join the scanned tree under tagged keys, frozen
    aux stays constant, and `make_linear` applies the method inside each layer.

    `bank`/`bank_profiles`/`bank_slots`: serving adapter bank — for each
    method group, per-request rows are gathered from the (K+1, L, …) resident
    leaves with `bank_slots[method]` (B,) ONCE here, outside the scan, and
    enter the scanned tree as (L, B, …) leaves; row K is the reserved zero
    row, so requests not using a method contribute exactly zero (methods are
    linear in their trainables — see core/adapter.py)."""
    eff = dict(layers)
    apps: Dict[str, List[SiteApp]] = {}
    method = adapter_api.resolve(peft.method)
    site_by_name = {s.name: s for s in sites}
    for full_name, ad in adapters.items():
        if not full_name.startswith(prefix):
            continue
        key = full_name[len(prefix):]
        site = site_by_name[full_name]
        if peft.strategy == "merged" and method.mergeable:
            method.merge_site(eff, key, ad, site, peft)
            continue
        tag = _app_tag("ad", method.name)
        trainable = set(method.trainable_leaves(peft))
        aux = {}
        for leaf, v in ad.items():
            if leaf in trainable:
                eff[key + tag + leaf] = v
            else:
                aux[leaf] = v
        apps.setdefault(key, []).append(SiteApp(tag, method, aux, peft))
    if bank and bank_slots is None:
        raise ValueError("adapter bank configured but the batch carries no "
                         "'adapter_slots' (Engine.generate builds them; "
                         "direct model calls must pass bank.slot_rows(...))")
    for mname in sorted(bank or ()):
        group = bank[mname]
        m = adapter_api.resolve(mname)
        prof = bank_profiles[mname]
        slots = bank_slots[mname]                      # (B,) rows incl. zero
        tag = _app_tag("bank", mname)
        for full_name, leaves in group["sites"].items():
            if not full_name.startswith(prefix):
                continue
            key = full_name[len(prefix):]
            for leaf, arr in leaves.items():           # (K+1, L, ...)
                gathered = jnp.take(arr, slots, axis=0)        # (B, L, ...)
                eff[key + tag + leaf] = jnp.moveaxis(gathered, 0, 1)
            apps.setdefault(key, []).append(
                SiteApp(tag, m, group["aux"].get(full_name, {}), prof,
                        banked=True))
    return eff, apps


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    d, L = cfg.d_model, cfg.num_layers
    ks = iter(jax.random.split(rng, 24))
    layers: Dict[str, jax.Array] = {
        "attn_norm": jnp.ones((L, d), dtype),
        "wq": dense_init(next(ks), (L, d, cfg.attn_dim), dtype),
        "wk": dense_init(next(ks), (L, d, cfg.kv_dim), dtype),
        "wv": dense_init(next(ks), (L, d, cfg.kv_dim), dtype),
        "wo": dense_init(next(ks), (L, cfg.attn_dim, d), dtype),
        "mlp_norm": jnp.ones((L, d), dtype),
    }
    if cfg.qkv_bias:
        layers["wq__b"] = jnp.zeros((L, cfg.attn_dim), dtype)
        layers["wk__b"] = jnp.zeros((L, cfg.kv_dim), dtype)
        layers["wv__b"] = jnp.zeros((L, cfg.kv_dim), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, cfg.head_dim), dtype)
        layers["k_norm"] = jnp.ones((L, cfg.head_dim), dtype)
    if cfg.moe is not None:
        e, f = cfg.moe.num_experts, cfg.moe.d_ff_expert
        layers["router"] = dense_init(next(ks), (L, d, e), jnp.float32)
        layers["we_i"] = dense_init(next(ks), (L, e, d, f), dtype)
        layers["we_g"] = dense_init(next(ks), (L, e, d, f), dtype)
        layers["we_o"] = dense_init(next(ks), (L, e, f, d), dtype)
    else:
        layers["wi"] = dense_init(next(ks), (L, d, cfg.d_ff), dtype)
        if cfg.gated_mlp:
            layers["wg"] = dense_init(next(ks), (L, d, cfg.d_ff), dtype)
        layers["wo_mlp"] = dense_init(next(ks), (L, cfg.d_ff, d), dtype)
    params: Dict = {"layers": layers, "final_norm": jnp.ones((d,), dtype)}
    if cfg.embed_inputs:
        if cfg.n_codebooks:
            params["embed"] = dense_init(next(ks), (cfg.n_codebooks, cfg.vocab, d), dtype)
        else:
            params["embed"] = dense_init(next(ks), (cfg.vocab, d), dtype)
    if cfg.n_codebooks:
        params["lm_head"] = dense_init(next(ks), (cfg.n_codebooks, d, cfg.vocab), dtype)
    else:
        params["lm_head"] = dense_init(next(ks), (d, cfg.vocab), dtype)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _embed(params: Dict, cfg: ModelConfig, batch: Dict) -> jax.Array:
    if not cfg.embed_inputs:
        return batch["embeds"].astype(jnp.dtype(cfg.dtype))
    tokens = batch["tokens"]
    if cfg.n_codebooks:
        # (B, S, CB): sum of per-codebook embeddings
        embs = [jnp.take(params["embed"][cb], tokens[..., cb], axis=0)
                for cb in range(cfg.n_codebooks)]
        return functools.reduce(jnp.add, embs)
    return jnp.take(params["embed"], tokens, axis=0)


def _attn_block(lp: Dict, x: jax.Array, cfg: ModelConfig, linear,
                positions: jax.Array, *, cache_kv=None, cache_pos=None,
                paged=None):
    """Pre-norm attention. If cache_kv=(k,v) is given, runs the decode path
    (append at cache_pos, attend over kv_len=cache_pos+1). A scalar
    cache_pos is the lockstep batch; a (B,) cache_pos is the per-slot path
    (continuous batching): each row writes its token at its own position
    and attends its own ragged kv_len. `paged=(block_table, attn_fn)` makes
    cache_kv a PAGE POOL pair ((P, ps, K, hd) per layer): each row's token
    is scattered into the page its block-table row maps the position to,
    and `attn_fn` (the registry-resolved paged_attention backend) gathers
    K/V through the block table."""
    B = x.shape[0]
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = linear(lp, "wq", h).reshape(B, -1, cfg.n_heads, cfg.head_dim)
    k = linear(lp, "wk", h).reshape(B, -1, cfg.n_kv, cfg.head_dim)
    v = linear(lp, "wv", h).reshape(B, -1, cfg.n_kv, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope)
    if cache_kv is None:
        att = attn_mod.attention(q, k, v, causal=True)
        new_kv = (k, v)        # post-RoPE, as stored by the decode path
    elif paged is not None:
        bt, attn_fn = paged
        pk, pv = cache_kv
        ps = pk.shape[1]
        # clamp keeps retired slots in-bounds (their block-table rows point
        # at the slot's reserved scratch page — dirt, never readable); write
        # targets are unique: each slot's current write page is uniquely
        # owned (decode positions lie beyond any shared prefix) and scratch
        # pages are per-slot
        idx = jnp.minimum(cache_pos, bt.shape[1] * ps - 1)
        page = jnp.take_along_axis(bt, (idx // ps)[:, None], axis=1)[:, 0]
        off = idx % ps
        pk = pk.at[page, off].set(k[:, 0].astype(pk.dtype),
                                  unique_indices=True,
                                  mode="promise_in_bounds")
        pv = pv.at[page, off].set(v[:, 0].astype(pv.dtype),
                                  unique_indices=True,
                                  mode="promise_in_bounds")
        att = attn_fn(q, pk, pv, bt, cache_pos + 1)
        new_kv = (pk, pv)
    else:
        ck, cv = cache_kv
        if jnp.ndim(cache_pos) == 0:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_pos, 0, 0))
        else:
            # per-slot scatter: row i writes at its own position. Clamp keeps
            # retired slots in-bounds — their rows are dead (kv_len masks
            # them; the next prime overwrites them). rows is an iota, so the
            # scatter hints (sorted/unique/in-bounds) apply and XLA lowers
            # this close to the lockstep dynamic_update_slice.
            idx = jnp.minimum(cache_pos, ck.shape[1] - 1)
            rows = jnp.arange(B)
            ck = ck.at[rows, idx].set(k[:, 0].astype(ck.dtype),
                                      indices_are_sorted=True,
                                      unique_indices=True,
                                      mode="promise_in_bounds")
            cv = cv.at[rows, idx].set(v[:, 0].astype(cv.dtype),
                                      indices_are_sorted=True,
                                      unique_indices=True,
                                      mode="promise_in_bounds")
        att = attn_mod.direct_attention(q, ck, cv, causal=False,
                                        kv_len=cache_pos + 1)
        new_kv = (ck, cv)
    out = linear(lp, "wo", att.reshape(B, -1, cfg.attn_dim))
    return x + out, new_kv


def _mlp_block(lp: Dict, x: jax.Array, cfg: ModelConfig, linear):
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.moe is not None:
        y, aux = moe_mod.moe_ffn(h, lp, cfg.moe, gated=cfg.gated_mlp)
        return x + y, aux
    hi = linear(lp, "wi", h)
    if cfg.gated_mlp:
        hg = linear(lp, "wg", h)
        hi = jax.nn.silu(hg.astype(jnp.float32)).astype(hi.dtype) * hi
    else:
        hi = jax.nn.gelu(hi.astype(jnp.float32)).astype(hi.dtype)
    return x + linear(lp, "wo_mlp", hi), jnp.float32(0.0)


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": save nothing


def forward(params: Dict, adapters: Dict, batch: Dict, cfg: ModelConfig,
            peft: PEFTConfig, sites, *, remat: str = "none",
            bank=None,
            bank_profiles=None) -> Tuple[jax.Array, jax.Array]:
    """Train/prefill forward. Returns (logits, moe_aux_loss)."""
    x = _embed(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)

    def body(carry, lp):
        x, aux = carry
        x, _ = _attn_block(lp, x, cfg, linear, positions)
        x, aux_l = _mlp_block(lp, x, cfg, linear)
        return (x, aux + aux_l), None

    (x, moe_aux), _ = jax.lax.scan(_remat(body, remat), (x, jnp.float32(0.0)),
                                   eff_layers)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,cdv->bscv", x, params["lm_head"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, moe_aux / cfg.num_layers


def loss_fn(params: Dict, adapters: Dict, batch: Dict, cfg: ModelConfig,
            peft: PEFTConfig, sites, *, remat: str = "none") -> jax.Array:
    logits, moe_aux = forward(params, adapters, batch, cfg, peft, sites,
                              remat=remat)
    ce = cross_entropy(logits, batch["labels"])
    if cfg.moe is not None:
        ce = ce + cfg.moe.aux_loss_weight * moe_aux
    return ce


# ---------------------------------------------------------------------------
# Prefill: one causal forward over the whole prompt that also populates the
# KV cache — replaces token-by-token teacher-forced stepping in the serving
# engine (S sequential decode dispatches -> one call, and attention runs
# parallel over S instead of S times over a masked cache).
# ---------------------------------------------------------------------------

def prefill(params: Dict, adapters: Dict, cache: Dict, batch: Dict,
            cfg: ModelConfig, peft: PEFTConfig, sites,
            bank=None,
            bank_profiles=None) -> Tuple[jax.Array, Dict]:
    """Process a (B, S) prompt against a fresh cache (pos must be 0).
    Returns (next_tokens after the last prompt token, cache at pos=S).

    batch["true_len"] (B,), optional: per-row real prompt length for
    right-padded prompts — next_tokens are read at position true_len-1
    instead of S-1, which makes a padded prefill EXACT for the valid rows
    (causality keeps positions < true_len independent of the pad tail; the
    pad tail's KV rows must then be masked by the caller via per-slot
    kv_len, see the continuous scheduler's prime path)."""
    x = _embed(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)

    # cache lives in the scan carry and is written in place per layer —
    # threading K/V through scan ys would materialize a second (L,B,S,K,hd)
    # stack next to the cache (see decode_step's carry note: ~3x-cache peak)
    def body(carry, lp_i):
        x, ck_all, cv_all = carry
        lp, li = lp_i
        x, (k, v) = _attn_block(lp, x, cfg, linear, positions)
        ck_all = jax.lax.dynamic_update_slice(
            ck_all, k.astype(ck_all.dtype)[None], (li, 0, 0, 0, 0))
        cv_all = jax.lax.dynamic_update_slice(
            cv_all, v.astype(cv_all.dtype)[None], (li, 0, 0, 0, 0))
        x, _ = _mlp_block(lp, x, cfg, linear)
        return (x, ck_all, cv_all), None

    (x, ck, cv), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (eff_layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    true_len = batch.get("true_len")
    if true_len is None:
        x = x[:, -1:]
    else:
        x = x[jnp.arange(B), true_len - 1][:, None]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,cdv->bscv", x, params["lm_head"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return next_tokens, {"k": ck, "v": cv, "pos": cache["pos"] + S}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, per_slot: bool = False) -> Dict:
    """per_slot=True allocates a (B,) position vector instead of the scalar
    — the persistent continuous-batching cache where every slot advances
    independently (decode_step picks the per-slot path off pos's rank)."""
    L = cfg.num_layers
    return {
        "k": jnp.zeros((L, batch, max_len, cfg.n_kv, cfg.head_dim), dtype),
        "v": jnp.zeros((L, batch, max_len, cfg.n_kv, cfg.head_dim), dtype),
        "pos": jnp.zeros((batch,) if per_slot else (), jnp.int32),
    }


def write_slot_cache(cache: Dict, slot_cache: Dict, slot, length) -> Dict:
    """In-flight prefill splice: write one primed request's KV (a batch-1
    scratch cache, P <= max_len rows) into slot row `slot` of a live
    per-slot cache and set that slot's position to `length`. Every other
    slot's rows and position are untouched, so the rest of the batch keeps
    decoding across the insertion; `slot`/`length` are traced scalars, so
    one compiled splice per scratch length serves every slot."""
    if cache["pos"].ndim != 1:
        raise ValueError("write_slot_cache needs a per_slot=True cache")
    k = jax.lax.dynamic_update_slice(
        cache["k"], slot_cache["k"].astype(cache["k"].dtype),
        (0, slot, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(
        cache["v"], slot_cache["v"].astype(cache["v"].dtype),
        (0, slot, 0, 0, 0))
    pos = cache["pos"].at[slot].set(jnp.asarray(length, jnp.int32))
    return {"k": k, "v": v, "pos": pos}


def reset_slots(cache: Dict, mask) -> Dict:
    """Retire slots: masked slots' positions return to 0, where decode
    leaves them until the next prime (their KV rows are left as-is — dead
    until the next write_slot_cache overwrites them, and unreadable
    meanwhile because kv_len masking never reaches them)."""
    if cache["pos"].ndim != 1:
        raise ValueError("reset_slots needs a per_slot=True cache")
    return {**cache, "pos": jnp.where(mask, 0, cache["pos"])}


# ---------------------------------------------------------------------------
# Paged KV cache (DESIGN.md §Paging): the per-slot decode path over a global
# pool of fixed-size pages instead of a dense (B, max_len) row per slot.
# Block tables and page lifecycle live host-side (serve/paging.py); this
# module owns the device math — pool init, COW page clone, the block-table
# decode path above, and the shared-prefix tail prefill.
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, dtype=jnp.bfloat16) -> Dict:
    """Page-pool cache: K/V live in (L, n_pages, page_size, K, hd) pools
    shared by every slot; `pos` stays the per-slot (B,) position vector.
    Slots map logical positions onto pages via the `block_table` the
    runtime passes per decode/prefill call — the pool itself is
    slot-agnostic."""
    L = cfg.num_layers
    shape = (L, n_pages, page_size, cfg.n_kv, cfg.head_dim)
    return {
        "pk": jnp.zeros(shape, dtype),
        "pv": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def copy_page(cache: Dict, src, dst) -> Dict:
    """Copy-on-write clone: duplicate physical page `src` into `dst` across
    every layer of both pools (pos untouched). The shared original is never
    written again — the borrower's tail prefill / decode writes land in the
    clone (DESIGN.md §Paging, COW rules)."""
    out = dict(cache)
    for key in ("pk", "pv"):
        pool = cache[key]
        page = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
        out[key] = jax.lax.dynamic_update_slice_in_dim(pool, page, dst,
                                                       axis=1)
    return out


def prefill_paged(params: Dict, adapters: Dict, cache: Dict, batch: Dict,
                  cfg: ModelConfig, peft: PEFTConfig, sites,
                  bank=None,
                  bank_profiles=None) -> Tuple[jax.Array, Dict]:
    """Shared-prefix tail prefill into the page pool: run ONLY the unshared
    tail of a prompt whose first `prefix_len` tokens are already resident
    in pages (reused via the prefix cache), writing the tail's KV through
    the block table. With prefix_len == 0 this is a full paged prefill —
    bit-identical (fp32) to the dense prefill + splice path.

    batch:
      tokens       (1, T)   right-padded tail tokens
      true_len     (1,)     optional real tail length (absent => T)
      block_table  (1, PPS) the slot's page map: shared prefix pages first,
                            then the slot's owned pages, scratch elsewhere
      window_table (1, WP)  leading slice of block_table covering the
                            resident prefix (WP pow2-bucketed by the
                            caller: the attention window costs
                            O(tail * WP*ps), not O(tail * max_len)).
                            ABSENT on a cold (no-reuse) prime — that is a
                            statically distinct graph which skips the page
                            window entirely (plain causal attention), so
                            0%-shared traffic pays no window-gather tax
      prefix_len   ()       reused prefix tokens already resident in pages
                            (present iff window_table is)
      slot         ()       slot row whose pos becomes prefix_len + true_len
      scratch_page ()       pad/overflow KV rows are routed to this page
                            (the slot's reserved scratch — dirt that decode
                            overwrites before it can ever be read)

    Returns (next_tokens (1,), cache) like `prefill`."""
    x = _embed(params, cfg, batch)
    B, T = x.shape[0], x.shape[1]
    wt = batch.get("window_table")
    with_window = wt is not None
    prefix_len = (jnp.asarray(batch["prefix_len"], jnp.int32) if with_window
                  else jnp.int32(0))
    positions = prefix_len + jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32), (B, T))
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)
    bt = batch["block_table"]                        # (1, PPS)
    ps = cache["pk"].shape[2]
    cap = bt.shape[1] * ps
    true_len = batch.get("true_len")
    tlen = (true_len[0] if true_len is not None
            else jnp.asarray(T, jnp.int32))
    # scatter targets: tail row j holds logical position prefix_len + j;
    # pad rows (j >= tlen) and overflow land in the slot's scratch page —
    # shared prefix pages are never written (tail positions start past
    # them), and decode overwrites any dirt before it becomes readable
    j = jnp.arange(T)
    logical = prefix_len + j
    valid = (j < tlen) & (logical < cap)
    safe = jnp.where(valid, logical, 0)
    w_page = jnp.where(valid, bt[0, safe // ps],
                       jnp.asarray(batch["scratch_page"], jnp.int32))
    w_off = jnp.where(valid, safe % ps, j % ps)

    def body(carry, lp_i):
        x, pk_all, pv_all = carry
        lp, li = lp_i
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = linear(lp, "wq", h).reshape(B, -1, cfg.n_heads, cfg.head_dim)
        k = linear(lp, "wk", h).reshape(B, -1, cfg.n_kv, cfg.head_dim)
        v = linear(lp, "wv", h).reshape(B, -1, cfg.n_kv, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        if cfg.rope_theta:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope)
        pk = jax.lax.dynamic_index_in_dim(pk_all, li, 0, keepdims=False)
        pv = jax.lax.dynamic_index_in_dim(pv_all, li, 0, keepdims=False)
        if with_window:
            # resident-prefix window, gathered through the window table
            # BEFORE the tail writes (the window only reads columns
            # < prefix_len, which the tail never touches)
            win = wt.shape[1] * ps
            kw = jnp.take(pk, wt[0], axis=0).reshape(1, win, cfg.n_kv,
                                                     cfg.head_dim)
            vw = jnp.take(pv, wt[0], axis=0).reshape(1, win, cfg.n_kv,
                                                     cfg.head_dim)
            att = attn_mod.prefix_attention(q, k, v, kw, vw, prefix_len)
        else:
            att = attn_mod.attention(q, k, v, causal=True)
        x = x + linear(lp, "wo", att.reshape(B, -1, cfg.attn_dim))
        # page-granular splice of the tail's KV (no unique/sorted claims:
        # pad rows may collide inside the scratch page — dirt either way)
        pk = pk.at[w_page, w_off].set(k[0].astype(pk.dtype),
                                      mode="promise_in_bounds")
        pv = pv.at[w_page, w_off].set(v[0].astype(pv.dtype),
                                      mode="promise_in_bounds")
        pk_all = jax.lax.dynamic_update_index_in_dim(pk_all, pk, li, 0)
        pv_all = jax.lax.dynamic_update_index_in_dim(pv_all, pv, li, 0)
        x, _ = _mlp_block(lp, x, cfg, linear)
        return (x, pk_all, pv_all), None

    (x, pk, pv), _ = jax.lax.scan(
        body, (x, cache["pk"], cache["pv"]),
        (eff_layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    x = x[jnp.arange(B), jnp.broadcast_to(tlen, (B,)) - 1][:, None]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    pos = cache["pos"].at[jnp.asarray(batch["slot"], jnp.int32)].set(
        prefix_len + tlen)
    return next_tokens, {"pk": pk, "pv": pv, "pos": pos}


def decode_step(params: Dict, adapters: Dict, cache: Dict, batch: Dict,
                cfg: ModelConfig, peft: PEFTConfig, sites,
                bank=None,
                bank_profiles=None) -> Tuple[jax.Array, Dict]:
    """One token for every sequence in the batch. batch: tokens (B, 1) (or
    embeds (B,1,d), positions (3,B,1) for vlm). Returns (next_tokens, cache).

    A paged cache (init_paged_cache: "pk"/"pv" page pools) rides the same
    per-slot path with batch["block_table"] (B, pages_per_seq) mapping each
    slot's logical positions onto pool pages; the attention backend is the
    registry-resolved `paged_attention` op (DESIGN.md §Paging)."""
    x = _embed(params, cfg, batch)
    B = x.shape[0]
    pos = cache["pos"]
    positions = batch.get("positions")
    if positions is None:
        if pos.ndim == 0:
            positions = jnp.broadcast_to(pos.astype(jnp.int32), (B, 1))
        else:                       # per-slot cache: row i sits at pos[i]
            positions = pos.astype(jnp.int32)[:, None]
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)
    paged = None
    if "pk" in cache:
        from repro.kernels import paged_attention as paged_mod
        op = kernel_api.resolve_op(
            "paged_attention", paged_mod.OWNER, peft,
            d1=cache["pk"].shape[2], d2=cfg.head_dim)
        paged = (batch["block_table"], op)
    kk, vk = ("pk", "pv") if paged is not None else ("k", "v")

    # cache lives in the scan CARRY and is updated in place per layer —
    # xs/ys threading would materialize two extra cache-sized buffers
    # (measured: decode peak ≈3× cache size, OOM on the 32k×128 cells)
    def body(carry, lp_i):
        x, ck_all, cv_all = carry
        lp, li = lp_i
        ck = jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)
        x, (ck, cv) = _attn_block(lp, x, cfg, linear, positions,
                                  cache_kv=(ck, cv), cache_pos=pos,
                                  paged=paged)
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, li, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, li, 0)
        x, _ = _mlp_block(lp, x, cfg, linear)
        return (x, ck_all, cv_all), None

    (x, ck, cv), _ = jax.lax.scan(
        body, (x, cache[kk], cache[vk]),
        (eff_layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,cdv->bscv", x, params["lm_head"])
        next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (B, CB)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
        next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (B,)
    if pos.ndim:
        # a reset slot (pos 0) holds no request — every prime leaves its
        # slot at pos >= 1 — so it stays at 0: its kv_len stays 1, and the
        # paged kernel visits one page for it instead of a window that
        # grows with every step it sits free
        pos = jnp.where(pos > 0, pos + 1, 0)
    else:
        pos = pos + 1
    new_cache = {kk: ck, vk: cv, "pos": pos}
    return next_tokens, new_cache


def advance_pos(cache: Dict, delta) -> Dict:
    """Host-driven per-slot position update for speculative decoding
    (DESIGN.md §Speculation): after a verify step the scheduler knows how
    many window tokens each slot accepted and advances `pos` by that delta
    (0 for retired slots); the drafter rolls its k probe steps back with a
    scalar -k. Clamped at 0 so retired slots (pos == 0) can never go
    negative and poison the scatter indices of the next step."""
    pos = cache["pos"] + jnp.asarray(delta, jnp.int32)
    return {**cache, "pos": jnp.maximum(pos, 0)}


def verify_step(params: Dict, adapters: Dict, cache: Dict, batch: Dict,
                cfg: ModelConfig, peft: PEFTConfig, sites,
                bank=None,
                bank_profiles=None) -> Tuple[jax.Array, Dict]:
    """Draft verification: one batched forward over a short window of W
    consecutive tokens per slot (DESIGN.md §Speculation). batch["tokens"]
    (B, W) holds [last accepted token, draft_1 .. draft_{W-1}] per row;
    row j sits at cache position pos + j. Returns (tokens (B, W), cache)
    where tokens[:, j] is the greedy continuation after consuming window
    token j — the scheduler accepts tokens[:, j] while draft_{j} ==
    tokens[:, j-1] and then calls `advance_pos` with the per-slot count.

    KV for ALL W window positions is written before attention (per layer),
    so the windowed paged_attention mask (col < kv_len + j) gives each
    query exactly the rows a step-by-step decode would see — greedy
    verification is bit-identical (fp32) to W sequential `decode_step`
    calls on the same drafts. `pos` is NOT advanced in-graph: acceptance is
    a host decision, and rejected rows simply stay past kv_len as dirt that
    the next window overwrites (rollback is bookkeeping, not data movement).

    Write routing (paged): position pos + j maps through the block table;
    entries past the slot's owned region default to its reserved scratch
    page, and absolute overflow (>= PPS*ps) is routed there explicitly via
    batch["scratch_pages"] (B,) — never clamped, so a deep slot's real rows
    can't be collided with. Dense caches scatter with mode="drop"."""
    x = _embed(params, cfg, batch)
    B, W = x.shape[0], x.shape[1]
    pos = cache["pos"]                              # (B,) per-slot
    positions = (pos.astype(jnp.int32)[:, None]
                 + jnp.arange(W, dtype=jnp.int32)[None, :])   # (B, W)
    eff_layers, apps = apply_peft_to_layers(
        params["layers"], adapters, sites, peft,
        bank=bank, bank_profiles=bank_profiles,
        bank_slots=batch.get("adapter_slots"))
    linear = make_linear(apps)
    paged = "pk" in cache
    kv_len = pos + 1                                # row-0 validity
    if paged:
        from repro.kernels import paged_attention as paged_mod
        op = kernel_api.resolve_op(
            "paged_attention", paged_mod.OWNER, peft,
            d1=cache["pk"].shape[2], d2=cfg.head_dim)
        bt = batch["block_table"]                   # (B, PPS)
        ps = cache["pk"].shape[2]
        cap = bt.shape[1] * ps
        scratch = batch.get("scratch_pages")
        if scratch is None:
            scratch = jnp.arange(B, dtype=jnp.int32)
        else:
            scratch = jnp.asarray(scratch, jnp.int32)
        valid = positions < cap
        safe = jnp.where(valid, positions, 0)
        w_page = jnp.where(valid, jnp.take_along_axis(bt, safe // ps, axis=1),
                           scratch[:, None])        # (B, W)
        w_off = jnp.where(valid, safe % ps,
                          jnp.arange(W, dtype=jnp.int32)[None, :] % ps)
    else:
        rows = jnp.arange(B)[:, None]               # (B, 1)
    kk, vk = ("pk", "pv") if paged else ("k", "v")

    def body(carry, lp_i):
        x, ck_all, cv_all = carry
        lp, li = lp_i
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = linear(lp, "wq", h).reshape(B, W, cfg.n_heads, cfg.head_dim)
        k = linear(lp, "wk", h).reshape(B, W, cfg.n_kv, cfg.head_dim)
        v = linear(lp, "wv", h).reshape(B, W, cfg.n_kv, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        if cfg.rope_theta:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope)
        ck = jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)
        if paged:
            # write-then-attend: all W rows land before the windowed mask
            # reads them (no unique claims: overflow rows may collide inside
            # the per-slot scratch page — dirt either way)
            ck = ck.at[w_page, w_off].set(k.astype(ck.dtype),
                                          mode="promise_in_bounds")
            cv = cv.at[w_page, w_off].set(v.astype(cv.dtype),
                                          mode="promise_in_bounds")
            att = op(q, ck, cv, bt, kv_len)
        else:
            ck = ck.at[rows, positions].set(k.astype(ck.dtype), mode="drop")
            cv = cv.at[rows, positions].set(v.astype(cv.dtype), mode="drop")
            att = attn_mod.windowed_decode_attention(q, ck, cv, kv_len)
        x = x + linear(lp, "wo", att.reshape(B, W, cfg.attn_dim))
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, li, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, li, 0)
        x, _ = _mlp_block(lp, x, cfg, linear)
        return (x, ck_all, cv_all), None

    (x, ck, cv), _ = jax.lax.scan(
        body, (x, cache[kk], cache[vk]),
        (eff_layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, W)
    return tokens, {kk: ck, vk: cv, "pos": pos}
