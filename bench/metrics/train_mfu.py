"""train_mfu: required training FLOPs per token x tokens/s of the traced
window, over chips x the chip's bf16 peak, in percent."""
from __future__ import annotations

from bench.metrics import _flops as flops


def read(ctx):
    if ctx.get("kind") != "train" or not ctx.get("tokens_per_s"):
        return None
    per_token = flops.train_flops_per_token(ctx["config"], ctx["seq"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_token * ctx["tokens_per_s"] / peak
