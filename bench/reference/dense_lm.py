"""Plain float32 reference of a dense decoder-only LM with FourierFT on the
attention projections, written from the published descriptions and sharing
no code with the program under test.

Architecture (Qwen3 / Llama family, HF `config.json` keys): token embedding;
per layer x += W_o attn(RoPE(norm_q(x W_q)), RoPE(norm_k(x W_k)), x W_v)
on the RMS-normed input, with grouped-query attention, causal softmax at
1/sqrt(head_dim), RoPE over half-split rotary pairs (theta = rope_theta) and,
where the file's `arch.qk_norm` says so (Qwen3), a per-head RMS norm of q
and k; then
x += W_down(silu(x W_gate) * (x W_up)) on the RMS-normed input; final RMS
norm; untied LM head; mean token cross-entropy.

FourierFT (Gao et al., ICML 2024, Eq. 2-4): the adapted weight is
W + ΔW with ΔW = α · Re(IDFT2(F)), F zero but for n spectral entries
(u_l, v_l) holding the trainable c_l. Written out,
ΔW[j, k] = α / (d1 d2) · Σ_l c_l cos(2π (j u_l / d1 + k v_l / d2)).

Every matmul runs at float32 `HIGHEST` precision. `matmul_fp8` is the same
arithmetic with both operands of every model matmul rounded to float8
(e4m3, per-tensor scale; gradients e5m2): the precision a bfloat16 program
would be tempted down to, used as the control that a sound comparison must
reject.

Parameters are read from the benchmark's own arrays, laid out as
{"base": {"layers": {name: (L, ...)}, "embed", "final_norm", "lm_head"},
 "peft": {"layers/wq": {"entries"}, "layers/wv": {"entries"}}} with the
coefficients {"layers/wq": (L, n), ...} passed apart.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def matmul_f32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _round(x, dtype, top: float):
    """x rounded to `dtype` under a per-tensor scale, back in float32."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def matmul_fp8(spec: str, a, b):
    """float8 arithmetic: operands rounded to e4m3, and in the backward pass
    the incoming gradient to e5m2, as an fp8 training step would."""
    return matmul_f32(spec, _round(a, jnp.float8_e4m3fn, E4M3_MAX),
                      _round(b, jnp.float8_e4m3fn, E4M3_MAX))


def _fp8_fwd(spec, a, b):
    qa = _round(a, jnp.float8_e4m3fn, E4M3_MAX)
    qb = _round(b, jnp.float8_e4m3fn, E4M3_MAX)
    return matmul_f32(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: matmul_f32(spec, x, y), qa, qb)
    return vjp(_round(g, jnp.float8_e5m2, E5M2_MAX))


matmul_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def fourier_delta(c, uv, d1: int, d2: int, alpha: float):
    """ΔW (d1, d2) for one layer's coefficients c (n,) at entries uv (2, n)."""
    j = jnp.arange(d1, dtype=jnp.int32)[:, None]
    k = jnp.arange(d2, dtype=jnp.int32)[:, None]
    # j*u < d1**2 stays exact in int32 for every width used here
    th = (2.0 * math.pi / d1) * ((j * uv[0][None, :]) % d1).astype(jnp.float32)
    ph = (2.0 * math.pi / d2) * ((k * uv[1][None, :]) % d2).astype(jnp.float32)
    c = c.astype(jnp.float32)
    dw = (jnp.matmul(jnp.cos(th) * c, jnp.cos(ph).T, precision=HIGHEST)
          - jnp.matmul(jnp.sin(th) * c, jnp.sin(ph).T, precision=HIGHEST))
    return dw * (alpha / (d1 * d2))


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x (B, S, N, hd), pos (B, S): rotate the pairs (i, i + hd/2)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Arch:
    """The sizes the reference needs, from a configuration file's keys."""

    def __init__(self, conf: Dict):
        c = conf["config"]
        self.d = c["hidden_size"]
        self.H = c["num_attention_heads"]
        self.K = c["num_key_value_heads"]
        self.hd = c.get("head_dim", self.d // self.H)
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.qk_norm = bool(conf["arch"]["qk_norm"])
        if not conf["arch"]["gated_mlp"]:
            raise ValueError("the reference has only the gated MLP")
        self.alpha = float(conf["peft"]["alpha"])


def _attention(mm, q, k, v, kv_valid):
    """Causal GQA softmax attention. q (B, S, H, hd), k/v (B, T, K, hd);
    query i sits at key position (T - S) + i; kv_valid (B, T) masks keys."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    g = H // K
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    qpos = (T - S) + jnp.arange(S)[:, None]
    mask = (jnp.arange(T)[None, :] <= qpos)[None, None] \
        & kv_valid[:, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm("bhqk,bkhd->bqhd", p, v)


def hidden_states(arch: Arch, base: Dict, entries: Dict, coefs: Dict,
                  tokens, mm=matmul_f32):
    """Final-normed hidden states (B, S, d) of `tokens` (B, S).
    `coefs[site]` is (L, n) for every adapted site, or (B, L, n) when each
    row carries its own adapter (serving tenants; a zero row is the base)."""
    B, S = tokens.shape
    x = jnp.take(base["embed"], tokens, axis=0).astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    valid = jnp.ones((B, S), bool)
    lw = base["layers"]
    per_row = any(c.ndim == 3 for c in coefs.values())
    cs = {s: (jnp.moveaxis(c, 1, 0) if c.ndim == 3 else c)
          for s, c in coefs.items()}

    def proj(w, name, cl, h):
        site = "layers/" + name
        y = mm("bsd,df->bsf", h, w[name])
        if site not in cl:
            return y
        d1, d2 = w[name].shape
        if per_row:
            dws = jax.vmap(lambda c: fourier_delta(c, entries[site], d1, d2,
                                                   arch.alpha))(cl[site])
            return y + mm("bsd,bdf->bsf", h, dws)
        return y + mm("bsd,df->bsf", h,
                      fourier_delta(cl[site], entries[site], d1, d2,
                                    arch.alpha))

    def body(x, layer):
        w, cl = layer
        h = rms_norm(x, w["attn_norm"], arch.eps)
        q = proj(w, "wq", cl, h).reshape(B, S, arch.H, arch.hd)
        k = proj(w, "wk", cl, h).reshape(B, S, arch.K, arch.hd)
        v = proj(w, "wv", cl, h).reshape(B, S, arch.K, arch.hd)
        if arch.qk_norm:
            q = rms_norm(q, w["q_norm"], arch.eps)
            k = rms_norm(k, w["k_norm"], arch.eps)
        q, k = rope(q, pos, arch.theta), rope(k, pos, arch.theta)
        a = _attention(mm, q, k, v, valid).reshape(B, S, arch.H * arch.hd)
        x = x + mm("bsa,ad->bsd", a, w["wo"])
        h = rms_norm(x, w["mlp_norm"], arch.eps)
        gate = mm("bsd,df->bsf", h, w["wg"])
        up = mm("bsd,df->bsf", h, w["wi"])
        x = x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["wo_mlp"])
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, (lw, cs))
    return rms_norm(x, base["final_norm"], arch.eps)


def logits(arch, base, entries, coefs, tokens, mm=matmul_f32):
    h = hidden_states(arch, base, entries, coefs, tokens, mm)
    return mm("bsd,dv->bsv", h, base["lm_head"])


def loss(arch: Arch, coefs: Dict, base: Dict, entries: Dict, tokens, labels,
         mm=matmul_f32):
    lg = logits(arch, base, entries, coefs, tokens, mm)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def adamw_step(arch: Arch, hp: Dict, mm=matmul_f32):
    """One AdamW step on the coefficients: clip the gradient to a global
    norm of hp["grad_clip"], lr warmed up linearly over hp["warmup_steps"]
    steps (the first step at lr / warmup_steps), then constant.
    -> step(coefs, mu, nu, t, base, entries, tokens, labels)
       = (coefs, mu, nu, loss, clipped grads); t counts from 1."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(coefs, mu, nu, t, base, entries, tokens, labels):
        l, g = jax.value_and_grad(loss, argnums=1)(arch, coefs, base, entries,
                                                   tokens, labels, mm)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(norm, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        lr = hp["learning_rate"] * jnp.minimum(
            t / max(hp["warmup_steps"], 1), 1.0)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        tf = t.astype(jnp.float32)
        coefs = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** tf))
            / (jnp.sqrt(v / (1 - b2 ** tf)) + eps), coefs, mu, nu)
        return coefs, mu, nu, l, g

    return step


def train(arch: Arch, hp: Dict, coefs0: Dict, base: Dict, entries: Dict,
          batches: Sequence[Tuple], mm=matmul_f32):
    """Run len(batches) reference steps from coefs0.
    -> (losses, first step's clipped gradients, coefficients after the last)"""
    step = jax.jit(adamw_step(arch, hp, mm))
    coefs = coefs0
    mu = jax.tree.map(jnp.zeros_like, coefs0)
    nu = jax.tree.map(jnp.zeros_like, coefs0)
    losses: List[float] = []
    g1 = None
    for t, (tokens, labels) in enumerate(batches, start=1):
        coefs, mu, nu, l, g = step(coefs, mu, nu, jnp.float32(t), base,
                                   entries, tokens, labels)
        losses.append(float(l))
        if g1 is None:
            g1 = g
    return losses, g1, coefs
