"""fourier_deltaw_roofline: the least time the chip could take for the
FourierFT ΔW kernels of the traced steps — per call the larger of its
FLOPs over the bf16 peak and its bytes over HBM bandwidth, from
`_flops.deltaw_work` — over the device time of those kernels (forward and
coefficient gradient, told apart as in `_kernels.py`), in percent.

One forward and one gradient call per adapted site per step; under a mesh
each chip runs its share of the layer stack, and both sides are averaged
over the chips."""
from __future__ import annotations

from bench.metrics import _flops as flops
from bench.metrics._kernels import FOURIER_DELTAW as KERNELS


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or tr is None:
        return None
    kernel_s = tr.op_seconds(KERNELS)
    steps = ctx.get("traced_steps")
    if not kernel_s or not steps:
        return None
    peaks = ctx["peaks"]
    least = 0.0
    for _, d1, d2, stack in ctx["sites"]:
        f, b = flops.deltaw_work(d1, d2, ctx["n"], stack)
        one = max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
        least += 2 * one                    # forward and gradient
    least = least * steps / ctx["chips"]
    return 100.0 * least / kernel_s
