"""Operation and byte counts from shapes, for the per-layer readers.

Required model FLOPs count only what the model's mathematics needs: 2 per
weight of every matmul (the LM head included) per token, plus attention's
q·kᵀ and p·v over each query's true causal context. Training adds the same
again for gradients to activations; frozen weights take no weight gradient.
Recomputation, ΔW materialization, padding and adapter work are not
counted, so the count does not depend on how a step is implemented.
"""
from __future__ import annotations


def _sizes(conf):
    c = conf["config"]
    d, H, K = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = c.get("head_dim", d // H)
    return c["num_hidden_layers"], d, H, K, hd, c["intermediate_size"], \
        c["vocab_size"]


def matmul_weights(conf) -> int:
    """Weights of the matmuls one token passes through (LM head included)."""
    L, d, H, K, hd, ff, V = _sizes(conf)
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    return L * per_layer + d * V


def attention_flops(conf, context: float) -> float:
    """q·kᵀ and p·v FLOPs of one token attending `context` keys."""
    L, d, H, K, hd, ff, V = _sizes(conf)
    return L * 4.0 * H * hd * context


def forward_flops_per_token(conf, seq: int) -> float:
    """Causal forward over rows of `seq` tokens, averaged per token."""
    return 2.0 * matmul_weights(conf) + attention_flops(conf, (seq + 1) / 2)


def train_flops_per_token(conf, seq: int) -> float:
    return 2.0 * forward_flops_per_token(conf, seq)


def deltaw_work(d1: int, d2: int, n: int, stack: int):
    """FourierFT ΔW of a stack of layers, from the algorithm: 4·d1·d2·n
    FLOPs and the float32 ΔW (written forward, read as the cotangent in the
    coefficient gradient) per layer. -> (flops, bytes)"""
    return 4.0 * d1 * d2 * n * stack, 4.0 * d1 * d2 * stack
