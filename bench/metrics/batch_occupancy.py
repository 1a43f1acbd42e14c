"""batch_occupancy: mean share of the decode slots busy over the decode
steps the scheduler ran in the window (`ServingMetrics.occupancy`), in
percent."""
from __future__ import annotations


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    occ = ctx["sched"]["occupancy"]
    return 100.0 * sum(occ) / len(occ) if occ else None
