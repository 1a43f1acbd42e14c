"""collective_exposed_frac: device time in collective ops while no other
op runs on that chip (`trace_reduce.TraceSummary.exposed_collective_s`,
with collectives told apart as in `_collectives.py`) over device busy
time, both summed over the chips, in percent. This is the part of the
exchanges that the step waits for. Nothing where the trace holds no
collective (one chip)."""
from __future__ import annotations

from bench.metrics import _collectives as col


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    chips = col.per_chip(trace)
    busy = col.busy_ns(trace)
    if not busy or not any(c for c, _ in chips):
        return None
    return 100.0 * sum(x for _, x in chips) / busy
