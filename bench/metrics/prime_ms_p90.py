"""prime_ms_p90: 90th percentile (nearest rank) of the scheduler's own
prime-prefill time (`ServingMetrics` prime_s, host clock ending in the
prime's host sync) over the requests that entered it in the window."""
from __future__ import annotations

import math


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    v = sorted(ctx["sched"]["prime_s"])
    if not v:
        return None
    return 1e3 * v[max(0, math.ceil(0.9 * len(v)) - 1)]
