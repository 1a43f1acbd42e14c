"""collective_share: device time in collective ops (told apart as in
`_collectives.py`) over device busy time, both summed over the chips, in
percent. Nothing where the trace holds no collective (one chip)."""
from __future__ import annotations

from bench.metrics import _collectives as col


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    coll = sum(c for c, _ in col.per_chip(trace))
    busy = col.busy_ns(trace)
    return 100.0 * coll / busy if coll and busy else None
