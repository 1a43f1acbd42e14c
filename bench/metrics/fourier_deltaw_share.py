"""fourier_deltaw_share: device time of the FourierFT ΔW kernels (forward
and coefficient gradient, told apart as in `_kernels.py`) over device
busy time, in percent."""
from __future__ import annotations

from bench.metrics._kernels import FOURIER_DELTAW as KERNELS


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.busy_s:
        return None
    k = tr.op_seconds(KERNELS)
    return 100.0 * k / tr.busy_s if k else None
