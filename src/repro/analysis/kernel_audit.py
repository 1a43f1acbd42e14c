"""Kernel-capability verifier (DESIGN.md §Analysis).

For every `KernelOp` in the registry that carries `caps` metadata
(kernels/api.py), re-derive from first principles what the declaration
claims, and fail when the declaration is LOOSER than the derivation:

- **int32 phase bound** (`deltaw_phase` caps — fourier_deltaw.py,
  dct_deltaw.py): the kernels reduce an integer phase product exactly in
  int32 (`j·u` mod d for the linear Fourier phase, `(2j+1)·u` mod 4d for
  the half-integer DCT phase). `j` runs over the BLOCK-PADDED row grid
  (ceil(d/bm)·bm rows), so the safe bound is below the naive ⌊√2³¹⌋ — the
  derivation here searches the exact largest `d` whose worst-case product
  stays under 2³¹, and the op's declared `max_dim` must not exceed it.
  A declared bound BELOW derived is conservative and fine (DCT declares
  32500 against a derived 32768).

- **VMEM footprint**: basis blocks + tile accumulator at the declared
  block sizes (×2 for double buffering; bases held in scratch count once,
  with the resident dc block beside them) must fit the 16 MB VMEM budget.

- **paged-attention scratch** (`paged_attention` caps): the declared
  online-softmax scratch dims must equal the canonical derivation —
  running max/denom one f32 per (K, W*G) pair, accumulator adding the
  head dim — and the per-grid-step working set must fit VMEM at the
  reference dims.

Ops without `caps` (einsum references, XLA-op backends) have nothing to
verify and are skipped.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.analysis.report import Finding

INT32_LIMIT = 2 ** 31
VMEM_BUDGET = 16 * 2 ** 20          # bytes per TPU core (v4/v5e class)
DOUBLE_BUFFER = 2


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


def _phase_product(d: int, bm: int, phase: str) -> int:
    """Worst-case integer phase product at dim `d`: the largest row index
    of the BLOCK-PADDED grid times the largest spectral index (< d)."""
    jmax = _ceil_to(d, bm) - 1
    umax = d - 1
    if phase == "linear":                 # fourier: j*u mod d
        return jmax * umax
    if phase == "half":                   # dct: (2j+1)*u mod 4d
        return (2 * jmax + 1) * umax
    raise ValueError(f"unknown phase kind {phase!r}")


def derived_phase_bound(caps: Dict) -> int:
    """Largest d whose worst-case phase product stays exactly representable
    in int32. The product is nondecreasing in d, so bisect."""
    bm = caps["bm"]
    phase = caps["phase"]
    lo, hi = 1, 1 << 17                   # bounds comfortably past sqrt(2^31)
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if _phase_product(mid, bm, phase) < INT32_LIMIT:
            lo = mid
        else:
            hi = mid - 1
    return lo


def derived_deltaw_vmem(caps: Dict) -> int:
    """VMEM bytes of the deltaw kernels at the declared block sizes: trig
    basis blocks for both axes, the (bm, bn) output tile, and the three
    (n,) entry vectors, all doubled for double buffering — or, where the
    caps declare `bases: "scratch"`, the bases once as scratch carried over
    the layer axis, the tile and entry blocks doubled, and the dc output
    resident for the whole call, one (n,) row per layer of `l_ref`."""
    bm, bn, n = caps["bm"], caps["bn"], caps["n_ref"]
    basis = caps["trig_terms"] * (bm + bn) * n * 4
    tile = bm * bn * 4
    entries = 3 * n * 4
    if caps.get("bases") == "scratch":
        return basis + DOUBLE_BUFFER * (tile + entries) + caps["l_ref"] * n * 4
    return DOUBLE_BUFFER * (basis + tile + entries)


_CANONICAL_SCRATCH = {"m": ("K", "W*G"), "l": ("K", "W*G"),
                      "acc": ("K", "W*G", "dh")}


def derived_paged_vmem(caps: Dict) -> int:
    """Per-grid-step VMEM bytes of the paged-attention kernel at the caps'
    reference dims: q/out window blocks, one K and one V page, and the f32
    online-softmax scratch — doubled for double buffering."""
    r = caps["ref"]
    K, G, W, dh, ps = r["K"], r["G"], r["W"], r["dh"], r["ps"]
    H = K * G
    qo = 2 * W * H * dh * 4               # q + out, f32 upper bound
    pages = 2 * ps * K * dh * 4           # one K page + one V page
    scratch = (2 * K * G * W + K * G * W * dh) * 4
    return DOUBLE_BUFFER * (qo + pages) + scratch


def audit_op(op) -> List[Finding]:
    """Verify one KernelOp's declared capabilities against the derivation.
    Ops without caps return no findings (nothing declared to check)."""
    caps = getattr(op, "caps", None)
    if not caps:
        return []
    where = f"{op.op}/{op.method}/{op.backend}"
    out: List[Finding] = []
    kind = caps.get("kind")
    if kind == "deltaw_phase":
        derived = derived_phase_bound(caps)
        if op.max_dim is None:
            out.append(Finding(
                "kernels", "bound-missing", where,
                f"phase caps declared but no max_dim on the op — the int32 "
                f"bound (derived {derived}) is unenforced"))
        elif op.max_dim > derived:
            out.append(Finding(
                "kernels", "bound-loosened", where,
                f"declared max_dim {op.max_dim} exceeds the derived int32 "
                f"phase bound {derived} (phase={caps['phase']}, "
                f"bm={caps['bm']}): dims in ({derived}, {op.max_dim}] "
                f"overflow the integer phase product"))
        vmem = derived_deltaw_vmem(caps)
        if vmem > VMEM_BUDGET:
            out.append(Finding(
                "kernels", "vmem-over-budget", where,
                f"derived per-step VMEM {vmem} B exceeds the "
                f"{VMEM_BUDGET} B budget at blocks "
                f"({caps['bm']}, {caps['bn']}, n={caps['n_ref']})"))
    elif kind == "paged_attention":
        declared = {k: tuple(v) for k, v in caps.get("scratch", {}).items()}
        if declared != _CANONICAL_SCRATCH:
            out.append(Finding(
                "kernels", "scratch-mismatch", where,
                f"declared scratch {declared} != canonical online-softmax "
                f"scratch {_CANONICAL_SCRATCH}"))
        vmem = derived_paged_vmem(caps)
        if vmem > VMEM_BUDGET:
            out.append(Finding(
                "kernels", "vmem-over-budget", where,
                f"derived per-step VMEM {vmem} B exceeds the "
                f"{VMEM_BUDGET} B budget at ref dims {caps['ref']}"))
    else:
        out.append(Finding(
            "kernels", "unknown-caps", where,
            f"unrecognized caps kind {kind!r} — the verifier cannot check "
            "this declaration; teach kernel_audit.py the new kind"))
    return out


def audit_registry(ops=None) -> List[Finding]:
    """Audit every registered KernelOp (or an explicit iterable — tests
    pass seeded-regression ops directly)."""
    if ops is None:
        from repro.kernels import api
        ops = api.all_ops()
    out: List[Finding] = []
    for op in ops:
        out += audit_op(op)
    return out


def declared_constants_findings() -> List[Finding]:
    """Cross-check the module-level declared constants against the caps
    derivation: ops.FOURIER_INT32_SAFE_DIM must equal the derived linear
    bound exactly (it was derived by measurement in PR 4 — drift means the
    tiling changed), ops.DCT_INT32_SAFE_DIM must not exceed the derived
    half-phase bound."""
    from repro.kernels import dct_deltaw, fourier_deltaw, ops
    out: List[Finding] = []
    f_derived = derived_phase_bound(fourier_deltaw.CAPS)
    if ops.FOURIER_INT32_SAFE_DIM != f_derived:
        out.append(Finding(
            "kernels", "constant-drift", "ops.FOURIER_INT32_SAFE_DIM",
            f"declared {ops.FOURIER_INT32_SAFE_DIM} != derived {f_derived} "
            f"for the linear phase at bm={fourier_deltaw.CAPS['bm']}"))
    d_derived = derived_phase_bound(dct_deltaw.CAPS)
    if ops.DCT_INT32_SAFE_DIM > d_derived:
        out.append(Finding(
            "kernels", "constant-drift", "ops.DCT_INT32_SAFE_DIM",
            f"declared {ops.DCT_INT32_SAFE_DIM} exceeds derived {d_derived} "
            f"for the half phase at bm={dct_deltaw.CAPS['bm']}"))
    return out


def run() -> List[Finding]:
    """The full kernel pass: registry audit + declared-constant cross-check."""
    return audit_registry() + declared_constants_findings()
