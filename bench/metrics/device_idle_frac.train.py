"""device_idle_frac.train: 1 - busy / traced window on the chips of a
training cell (busy: the union of device op intervals), in percent."""
from __future__ import annotations


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or tr is None:
        return None
    idle = tr.idle_frac()
    return None if idle is None else 100.0 * idle
