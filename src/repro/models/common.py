"""Shared model building blocks: norms, RoPE (incl. M-RoPE), inits."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def head_rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head qk-norm (qwen3/olmoe): normalizes the trailing head_dim."""
    return rms_norm(x, w, eps)


def dense_init(rng: jax.Array, shape, dtype, scale: float = 0.02) -> jax.Array:
    """Normal(0, scale²) drawn directly in `dtype`: a bf16 weight never has
    a float32 copy."""
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(scale, dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    """Qwen2-VL section split of the rotary half-dim among (t, h, w) position
    streams — (16, 24, 24) for head_dim 128."""
    half = head_dim // 2
    hw = (3 * half) // 8
    return (half - 2 * hw, hw, hw)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope: bool = False) -> jax.Array:
    """x: (B, S, N, head_dim). positions: (B, S) int32, or (3, B, S) for M-RoPE."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    inv = rope_freqs(head_dim, theta)                      # (half,)
    if mrope:
        sec = mrope_sections(head_dim)
        pos = positions.astype(jnp.float32)                 # (3, B, S)
        idx = jnp.concatenate([
            jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sec)])
        pos_per_dim = jnp.take(pos, idx, axis=0)            # (half, B, S)
        angles = jnp.einsum("hbs,h->bsh", pos_per_dim, inv)  # (B, S, half)
    else:
        angles = positions.astype(jnp.float32)[..., None] * inv  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]                    # (B, S, 1, half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 0.0) -> jax.Array:
    """Mean token CE in f32. logits (..., V), labels (...) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return jnp.mean(loss)
