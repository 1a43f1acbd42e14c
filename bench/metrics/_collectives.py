"""How the device trace tells collectives apart, and their time per chip.

A collective is an op whose own HLO opcode, or whose instruction name,
is one of XLA's collectives (`all-reduce`, `all-gather`, `reduce-scatter`,
`collective-permute`, `all-to-all`, and their async `-start` / `-done`
halves): `trace_reduce.COLLECTIVE` applied to the op itself, not to the
whole label, whose operand list may name a collective's result. Compute
is every other op but the containers (`trace_reduce.CONTAINERS`, a scan's
while loop), which hold the ops of their body. A collective that the TPU
compiler fuses into a compute op (an "async collective fusion": an
all-gather run under a matmul) shows in the trace as that fusion alone,
so it counts as compute: hidden, and not in the collective time.

Each chip's intervals are sorted once and swept, so a window of a million
ops reduces in seconds (the reduction's own interval subtraction compares
every pair)."""
from __future__ import annotations

from typing import List, Tuple

from bench import trace_reduce as tr


def is_collective(op: tr.Op) -> bool:
    name = op.name.split(" = ", 1)[0].lstrip("%")
    return bool(tr.COLLECTIVE.search(op.opcode)
                or tr.COLLECTIVE.match(name))


def overlap(a: List[tr.Interval], b: List[tr.Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def per_chip(trace: tr.TraceSummary) -> List[Tuple[float, float]]:
    """(collective, exposed collective) nanoseconds of each chip in the
    window; exposed is collective time while no compute runs."""
    lo, hi = trace.window
    out = []
    for d in trace.devices:
        coll, comp = [], []
        for o in d.ops:
            s, e = max(o.start, lo), min(o.end, hi)
            if e <= s:
                continue
            if is_collective(o):
                coll.append((s, e))
            elif o.opcode not in tr.CONTAINERS:
                comp.append((s, e))
        coll = tr.union(coll)
        c = tr.total(coll)
        out.append((c, c - overlap(coll, tr.union(comp))))
    return out


def busy_ns(trace: tr.TraceSummary) -> float:
    """Busy nanoseconds summed over the chips."""
    return trace.busy_s * 1e9 * len(trace.devices)
