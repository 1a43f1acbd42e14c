"""Compiled-HLO analyzer (DESIGN.md §Dist).

XLA's `compiled.cost_analysis()` visits while bodies exactly once, which makes
it useless for scanned programs (a 32-layer scan reports 1 layer of flops).
This module re-derives per-device cost from the optimized HLO text with full
call-graph multiplicity:

- `while` bodies scale by the trip count (`backend_config known_trip_count`
  when present, else the constant bound in the condition's ROOT compare);
- `fusion` / `call` / `conditional` computations are inlined at the caller's
  multiplicity (conditional branches are all charged — an upper bound);
- `reduce`/`sort`/collective `to_apply` reducers are NOT recursed into (they
  run per element and are charged at the call site instead).

Byte accounting reports two bounds:

- `bytes` — CPU-fusion-granularity upper bound: every non-trivial
  instruction reads its operands and writes its output;
- `bytes_min` — TPU-fusion-ideal lower bound: only materializing ops
  (dot/conv/reduce/collectives/copies/slice-updates/gather/scatter/
  custom-call) touch HBM; elementwise chains are assumed fully fused.

Collective traffic is the output size of each collective × multiplicity,
broken down by kind in `bytes_by_kind` / `count_by_kind` — all-to-all and
collective-permute get their own buckets, never lumped into a generic
"collective" bin. `group_by_kind` additionally records the largest
replica-group size seen per kind (both `{{0,1},…}` literal and `[G,S]<=[N]`
iota forms; permute pairs count as groups of 2).

`analysis/hlo_lint.py` reads the parser (`parse_module`, `iter_instrs`,
`custom_call_target`, `aliased_params`); `analyze_module`'s FLOP/byte walk
is read only by its tests.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

# operand tokens: optional inline shape, then %var
_OPERAND_RE = re.compile(r"(?:([\w\-]+\[[\d,]*\](?:\{[\d,]*\})?)\s+)?%([\w\.\-]+)")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "and", "or", "xor", "not", "negate", "abs", "sign", "exponential",
    "exponential-minus-one", "log", "log-plus-one", "tanh", "sqrt", "rsqrt",
    "cbrt", "sine", "cosine", "tan", "atan2", "logistic", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "is-finite", "erf",
    "select", "clamp", "compare", "convert", "remainder", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "popcnt", "clz",
    "stochastic-convert", "real", "imag", "complex",
}

_ZERO_COST = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "reshape", "after-all", "opt-barrier", "domain",
    "partition-id", "replica-id", "iota", "broadcast", "transpose",
}

_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
}

# materializing ops for the fusion-ideal lower bound
_MATERIALIZE = {
    "dot", "convolution", "reduce", "reduce-window", "copy", "sort",
    "dynamic-slice", "dynamic-update-slice", "slice", "pad", "concatenate",
    "gather", "scatter", "custom-call", "rng", "rng-bit-generator",
    "cholesky", "triangular-solve", "fft",
} | _COLLECTIVES


def _shape_elems(s: str) -> int:
    n = 0
    for _, dims in _SHAPE_RE.findall(s):
        e = 1
        for d in dims.split(","):
            if d:
                e *= int(d)
        n += e
    return n


def _shape_bytes(s: str) -> int:
    """Byte size of an HLO shape string: 'f32[128,256]{1,0}', 'bf16[2,4]',
    tuples '(f32[4], s32[2,2])', scalars 'pred[]'. Layout suffixes are
    ignored; unknown element types (token, opaque) count 0."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(s):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * width
    return total


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    operands: List[Tuple[Optional[str], str]]   # (inline shape | None, var)
    attrs: str
    body: str = ""                              # raw operand text


@dataclasses.dataclass
class ModuleStats:
    flops: float = 0.0
    dot_flops: float = 0.0
    bytes: float = 0.0
    bytes_min: float = 0.0
    collective_bytes: float = 0.0
    bytes_by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)
    count_by_kind: Dict[str, int] = dataclasses.field(default_factory=dict)
    group_by_kind: Dict[str, int] = dataclasses.field(default_factory=dict)


_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_PERMUTE_PAIRS_RE = re.compile(r"source_target_pairs=\{\{")


def replica_group_size(attrs: str) -> Optional[int]:
    """Replica-group size of a collective from its attrs. Handles the
    literal form `replica_groups={{0,1},{2,3}}` (size = first group's
    length), the iota form `replica_groups=[G,S]<=[N]` (size = S), and
    collective-permute's `source_target_pairs` (pairwise: 2)."""
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        return int(m.group(2))
    m = _GROUPS_LITERAL_RE.search(attrs)
    if m:
        return len(m.group(1).split(","))
    if _PERMUTE_PAIRS_RE.search(attrs):
        return 2
    return None


def _split_shape(rest: str) -> Tuple[str, str]:
    """Split '<shape> <rest>' where shape may be a parenthesized tuple."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].strip()
    shape, _, tail = rest.partition(" ")
    return shape, tail


def _paren_body(s: str) -> Tuple[str, str]:
    """s starts at '('; return (inside, after)."""
    depth = 0
    for i, ch in enumerate(s):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return s[1:i], s[i + 1:]
    return s[1:], ""


def _parse_instr(line: str) -> Optional[Instr]:
    ls = line.strip()
    if ls.startswith("ROOT "):
        ls = ls[5:]
    if not ls.startswith("%") or " = " not in ls:
        return None
    name, _, rest = ls.partition(" = ")
    shape, rest = _split_shape(rest)
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return None
    opcode = m.group(1)
    body, attrs = _paren_body(rest[m.end() - 1:])
    operands = [(s, v) for s, v in _OPERAND_RE.findall(body)]
    return Instr(name.lstrip("%"), shape, opcode,
                 [(s or None, v) for s, v in operands], attrs, body)


def _parse_module(txt: str) -> Tuple[Dict[str, List[Instr]], Optional[str]]:
    comps: Dict[str, List[Instr]] = {}
    entry = None
    cur: Optional[List[Instr]] = None
    for raw in txt.splitlines():
        line = raw.rstrip()
        ls = line.strip()
        if not ls:
            continue
        if ls.endswith("{") and " = " not in ls:
            m = re.match(r"(ENTRY\s+)?%?([\w\.\-]+)\s*\(", ls)
            if m:
                cur = []
                comps[m.group(2)] = cur
                if m.group(1):
                    entry = m.group(2)
            continue
        if ls == "}":
            cur = None
            continue
        if cur is not None:
            instr = _parse_instr(ls)
            if instr is not None:
                cur.append(instr)
    if entry is None and comps:                  # bare snippet fallback
        entry = next(iter(comps))
    return comps, entry


def _called(attrs: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w\.\-]+)", attrs)
    return m.group(1) if m else None


def _trip_count(instr: Instr, comps: Dict[str, List[Instr]]) -> int:
    """While trip count: backend_config known_trip_count if the compiler
    resolved it, else the constant bound in the condition's ROOT compare
    (scan/fori_loop lower to `iter < C`). Unknown bounds count once."""
    m = re.search(r'known_trip_count[^}]*"n"\s*:\s*"(\d+)"', instr.attrs)
    if m:
        return int(m.group(1))
    cond = _called(instr.attrs, "condition")
    if cond and cond in comps:
        for ins in comps[cond]:
            if ins.opcode == "compare":
                for _, var in ins.operands:
                    val = _const_value(var, comps[cond])
                    if val is not None and val > 0:
                        if "direction=LE" in ins.attrs:
                            return val + 1
                        return val
    return 1


def _const_value(var: str, instrs: List[Instr]) -> Optional[int]:
    for ins in instrs:
        if ins.name == var and ins.opcode == "constant":
            m = re.fullmatch(r"\s*(-?\d+)\s*", ins.body)
            if m:
                return int(m.group(1))
    return None


def _operand_bytes(instr: Instr, table: Dict[str, str]) -> int:
    total = 0
    for shp, var in instr.operands:
        s = shp or table.get(var)
        if s:
            total += _shape_bytes(s)
    return total


def _operand_shape(instr: Instr, idx: int,
                   table: Dict[str, str]) -> Optional[str]:
    if idx >= len(instr.operands):
        return None
    shp, var = instr.operands[idx]
    return shp or table.get(var)


def _dims_of(shape: str) -> List[int]:
    m = _SHAPE_RE.search(shape or "")
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _dot_flops(instr: Instr, table: Dict[str, str]) -> float:
    out = _shape_elems(instr.shape)
    lhs = _operand_shape(instr, 0, table)
    contract = 1
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.attrs)
    if m and lhs:
        dims = _dims_of(lhs)
        for d in m.group(1).split(","):
            if d and int(d) < len(dims):
                contract *= dims[int(d)]
    return 2.0 * out * contract


def _conv_flops(instr: Instr, table: Dict[str, str]) -> float:
    out = _shape_elems(instr.shape)
    rhs = _operand_shape(instr, 1, table)
    kdims = _dims_of(rhs) if rhs else []
    kernel = 1
    for d in kdims:
        kernel *= d
    # divide out the kernel's output-feature dim when identifiable
    m = re.search(r"dim_labels=\w+_(\w+)->", instr.attrs)
    if m and kdims and "o" in m.group(1):
        kernel //= max(kdims[m.group(1).index("o")], 1)
    return 2.0 * out * kernel


def _walk(comp: str, mult: float, comps: Dict[str, List[Instr]],
          stats: ModuleStats, is_entry: bool) -> None:
    instrs = comps.get(comp, [])
    table = {i.name: i.shape for i in instrs}
    for ins in instrs:
        op = ins.opcode
        out_b = _shape_bytes(ins.shape)
        kind = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done"):
            continue
        if kind in _COLLECTIVES:
            stats.collective_bytes += mult * out_b
            stats.bytes_by_kind[kind] = (stats.bytes_by_kind.get(kind, 0.0)
                                         + mult * out_b)
            stats.count_by_kind[kind] = (stats.count_by_kind.get(kind, 0)
                                         + int(round(mult)))
            gs = replica_group_size(ins.attrs)
            if gs is not None:
                stats.group_by_kind[kind] = max(
                    stats.group_by_kind.get(kind, 0), gs)
            stats.bytes += mult * (out_b + _operand_bytes(ins, table))
            stats.bytes_min += mult * out_b
            continue
        if op == "while":
            trip = _trip_count(ins, comps)
            body = _called(ins.attrs, "body")
            cond = _called(ins.attrs, "condition")
            if body:
                _walk(body, mult * trip, comps, stats, False)
            if cond:
                _walk(cond, mult * trip, comps, stats, False)
            continue
        if op == "conditional":
            branches = []
            if "branch_computations" in ins.attrs:
                blob = ins.attrs.split("branch_computations", 1)[1]
                blob = blob.split("}", 1)[0]
                branches = re.findall(r"%([\w\.\-]+)", blob)
            branches += [b for b in (_called(ins.attrs, "true_computation"),
                                     _called(ins.attrs, "false_computation"))
                         if b]
            for branch in branches:
                _walk(branch, mult, comps, stats, False)
            continue
        if op == "fusion":
            called = _called(ins.attrs, "calls")
            if called:
                _walk(called, mult, comps, stats, False)
            stats.bytes += mult * (out_b + _operand_bytes(ins, table))
            stats.bytes_min += mult * (out_b + _operand_bytes(ins, table))
            continue
        if op == "call":
            called = _called(ins.attrs, "to_apply")
            if called:
                _walk(called, mult, comps, stats, False)
            continue
        if op == "parameter":
            if is_entry:
                stats.bytes += out_b
                stats.bytes_min += out_b
            continue
        if op == "dot":
            f = _dot_flops(ins, table)
            stats.dot_flops += mult * f
            stats.flops += mult * f
            stats.bytes += mult * (out_b + _operand_bytes(ins, table))
            stats.bytes_min += mult * (out_b + _operand_bytes(ins, table))
            continue
        if op == "convolution":
            f = _conv_flops(ins, table)
            stats.dot_flops += mult * f
            stats.flops += mult * f
            stats.bytes += mult * (out_b + _operand_bytes(ins, table))
            stats.bytes_min += mult * (out_b + _operand_bytes(ins, table))
            continue
        if op in _ZERO_COST:
            continue
        if op in ("reduce", "reduce-window"):
            stats.flops += mult * max(_shape_elems(
                _operand_shape(ins, 0, table) or ins.shape), _shape_elems(ins.shape))
            stats.bytes += mult * (out_b + _operand_bytes(ins, table))
            stats.bytes_min += mult * (out_b + _operand_bytes(ins, table))
            continue
        if op in _ELEMENTWISE:
            stats.flops += mult * _shape_elems(ins.shape)
            stats.bytes += mult * (out_b + _operand_bytes(ins, table))
            continue
        # everything else (copies, slices, custom-calls, rng, …)
        stats.bytes += mult * (out_b + _operand_bytes(ins, table))
        if op in _MATERIALIZE:
            stats.bytes_min += mult * (out_b + _operand_bytes(ins, table))


def analyze_module(txt: str) -> ModuleStats:
    """Analyze optimized HLO text (`compiled.as_text()`); for SPMD-partitioned
    modules the result is already per-device."""
    comps, entry = _parse_module(txt)
    stats = ModuleStats()
    if entry is not None:
        _walk(entry, 1.0, comps, stats, True)
    return stats


# ---------------------------------------------------------------------------
# Structural accessors for the static analyzer (repro.analysis)
# ---------------------------------------------------------------------------

def parse_module(txt: str) -> Tuple[Dict[str, List[Instr]], Optional[str]]:
    """Public parse: `compiled.as_text()` -> ({computation: [Instr]}, entry).
    Same parser the cost walk uses; repro.analysis lints over it."""
    return _parse_module(txt)


def iter_instrs(comps: Dict[str, List[Instr]], entry: Optional[str]):
    """Yield (instr, multiplicity, in_loop) over the entry call graph with
    the same inlining rules as the cost walk: while bodies/conditions at the
    trip-count multiplicity (and flagged `in_loop`), fusion/call/conditional
    computations at the caller's multiplicity, reduce/sort `to_apply`
    reducers not recursed. Cycles are cut (each computation is entered once
    per distinct call path, bounded by the acyclic HLO call graph)."""
    if entry is None:
        return

    def rec(comp: str, mult: float, in_loop: bool, seen: Tuple[str, ...]):
        if comp in seen:
            return
        seen = seen + (comp,)
        for ins in comps.get(comp, []):
            op = ins.opcode
            if op == "while":
                trip = _trip_count(ins, comps)
                yield ins, mult, in_loop
                for key in ("body", "condition"):
                    c = _called(ins.attrs, key)
                    if c:
                        yield from rec(c, mult * trip, True, seen)
                continue
            if op == "conditional":
                branches = []
                if "branch_computations" in ins.attrs:
                    blob = ins.attrs.split("branch_computations", 1)[1]
                    blob = blob.split("}", 1)[0]
                    branches = re.findall(r"%([\w\.\-]+)", blob)
                branches += [b for b in
                             (_called(ins.attrs, "true_computation"),
                              _called(ins.attrs, "false_computation")) if b]
                yield ins, mult, in_loop
                for b in branches:
                    yield from rec(b, mult, in_loop, seen)
                continue
            if op == "fusion":
                c = _called(ins.attrs, "calls")
                if c:
                    yield from rec(c, mult, in_loop, seen)
                yield ins, mult, in_loop
                continue
            if op == "call":
                c = _called(ins.attrs, "to_apply")
                if c:
                    yield from rec(c, mult, in_loop, seen)
                yield ins, mult, in_loop
                continue
            yield ins, mult, in_loop

    yield from rec(entry, 1.0, False, ())


def custom_call_target(instr: Instr) -> Optional[str]:
    """custom_call_target of a custom-call Instr, None otherwise."""
    m = re.search(r'custom_call_target="([^"]+)"', instr.attrs)
    return m.group(1) if m else None


def aliased_params(txt: str) -> set:
    """Entry parameter numbers the module's `input_output_alias` header maps
    an output onto. XLA drops donated-but-unusable buffers from the header
    entirely (the donation was wasted — the input buffer stays live), which
    is exactly what repro.analysis's donation pass checks for."""
    m = re.search(r"input_output_alias=\{", txt)
    if not m:
        return set()
    i = m.end() - 1
    depth = 0
    for j in range(i, len(txt)):
        depth += txt[j] == "{"
        depth -= txt[j] == "}"
        if depth == 0:
            break
    blob = txt[i:j + 1]
    return {int(p) for p in re.findall(r"\((\d+),\s*\{", blob)}


def entry_param_count(txt: str) -> int:
    """Number of `parameter(N)` instructions in the entry computation."""
    comps, entry = _parse_module(txt)
    if entry is None:
        return 0
    return sum(1 for ins in comps.get(entry, [])
               if ins.opcode == "parameter")
