"""serve_mfu: required base-model forward FLOPs of the tokens the chip
processed in the traced window — the prompts primed in it (a request's
prompt counts when its first token arrives in the window) and every output
token streamed in it, each over its true causal context — over window x
chips x the chip's bf16 peak, in percent. Adapter work is not counted."""
from __future__ import annotations

from bench.metrics import _flops as flops


def read(ctx):
    if ctx.get("kind") != "serve" or ctx.get("trace") is None:
        return None
    conf, w0, w1 = ctx["config"], ctx["w0"], ctx["w1"]
    weights = 2.0 * flops.matmul_weights(conf)
    total = 0.0
    for r, p in zip(ctx["results"], ctx["planned"]):
        P = len(p.prompt)
        for i, t in enumerate(r["stamps"]):
            if not w0 <= t < w1:
                continue
            if i == 0:
                total += P * weights + P * flops.attention_flops(
                    conf, (P + 1) / 2)
            else:
                total += weights + flops.attention_flops(conf, P + i)
    if not total:
        return None
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * total / ((w1 - w0) * peak)
