"""paged_attention_share: device time of the paged-attention kernel over
device busy time, in percent (told apart as in `_kernels.py`)."""
from __future__ import annotations

from bench.metrics._kernels import PAGED_ATTENTION


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.busy_s:
        return None
    k = tr.op_seconds(PAGED_ATTENTION)
    return 100.0 * k / tr.busy_s if k else None
