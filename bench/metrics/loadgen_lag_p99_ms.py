"""loadgen_lag_p99_ms: 99th percentile (nearest rank) of how late the load
generator sent the window's requests after their due time."""
from __future__ import annotations


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    lag = ctx["window"]["loadgen_lag_p99_ms"]
    return lag if lag == lag else None
