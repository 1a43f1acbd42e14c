"""Token-choice top-k MoE with per-sequence static capacity (2-D parallel:
experts over `model`, sequences over `data`).

Slot assignment is PER SEQUENCE: dispatch buffers are (B, E, C_seq, d), so the
scatter carries the batch dim in both source and target — GSPMD partitions it
along `data` without any global redistribution, and the expert einsum runs
2-D-parallel. (A single global capacity pool needs a global cumsum over
tokens and an all-layout scatter; measured on olmoe train_4k: either 16x
redundant expert FLOPs — capacity dim unsharded — or 200s+ of collectives.)
The combine side needs no scatter at all: every (token, k) contribution is
gathered back and reduced over k.

Auxiliary load-balance loss (Switch): E * Σ_e f_e · P_e over all tokens.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig


def capacity(tokens_per_group: int, cfg: MoEConfig) -> int:
    c = int(math.ceil(cfg.capacity_factor * tokens_per_group * cfg.top_k
                      / cfg.num_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling friendliness


def route(router_logits: jax.Array, cfg: MoEConfig):
    """router_logits (..., E) -> gates (..., k), ids (..., k), aux scalar."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    E = probs.shape[-1]
    flat_ids = ids.reshape(-1)
    f = jnp.zeros((E,), jnp.float32).at[flat_ids].add(1.0) / flat_ids.size
    p = probs.reshape(-1, E).mean(axis=0)
    aux = E * jnp.sum(f * p)
    return gates, ids, aux


def assign_slots(ids: jax.Array, num_experts: int, cap: int):
    """Greedy position-in-expert assignment honoring top-k priority order.
    ids (T, k) -> slots (T, k) int32, keep (T, k) bool."""
    T, k = ids.shape
    slots = []
    counts = jnp.zeros((num_experts,), jnp.int32)
    for j in range(k):  # k is small and static; unrolled
        oh = jax.nn.one_hot(ids[:, j], num_experts, dtype=jnp.int32)
        pos = jnp.cumsum(oh, axis=0) * oh                    # 1-based within oh
        slot = (pos.sum(-1) - 1) + counts[ids[:, j]]
        counts = counts + oh.sum(axis=0)
        slots.append(slot)
    slots = jnp.stack(slots, axis=1)
    keep = slots < cap
    return slots.astype(jnp.int32), keep


def moe_ffn(x: jax.Array, p: Dict[str, jax.Array], cfg: MoEConfig,
            gated: bool = True) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d); p: router (d,E), we_i/we_g (E,d,f), we_o (E,f,d).
    Returns (y (B,S,d), aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    gates, ids, aux = route(logits, cfg)                 # (B, S, k)
    cap = capacity(S, cfg)
    slots, keep = jax.vmap(
        lambda i: assign_slots(i, E, cap))(ids)          # (B, S, k)

    # ---- dispatch: batched scatter into (B, E, C, d) ----
    contrib = jnp.where(keep[..., None], x[:, :, None, :], 0)  # (B,S,k,d)

    def scatter_one(eb, sb, cb):
        buf = jnp.zeros((E, cap, d), x.dtype)
        return buf.at[eb.reshape(-1), sb.reshape(-1)].add(
            cb.reshape(-1, d).astype(x.dtype), mode="drop")

    buf = jax.vmap(scatter_one)(ids, slots, contrib)     # (B, E, C, d)
    # ---- expert FFN ----
    h = jnp.einsum("becd,edf->becf", buf, p["we_i"])
    if gated:
        g = jnp.einsum("becd,edf->becf", buf, p["we_g"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    out = jnp.einsum("becf,efd->becd", h, p["we_o"])
    # ---- combine: batched gather + weighted reduce over k (no scatter) ----
    def gather_one(ob, eb, sb):
        return ob[eb.reshape(-1), sb.reshape(-1)].reshape(S, k, d)

    gathered = jax.vmap(gather_one)(out, ids, slots)     # (B, S, k, d)
    w = (gates * keep).astype(jnp.float32)               # (B, S, k)
    y = jnp.einsum("bskd,bsk->bsd", gathered.astype(jnp.float32), w)
    return y.astype(x.dtype), aux
