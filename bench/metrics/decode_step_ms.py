"""decode_step_ms: mean device duration of one execution of the serving
decode program (`jit_decode_step`, the XLA module of `decode_step`) in the
traced window, from the trace's module line."""
from __future__ import annotations


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    calls = tr.module_calls(r"^jit_decode_step\b")
    if not calls:
        return None
    return 1e-6 * sum(c.dur for c in calls) / len(calls)
