"""Pluggable kernel-op registry + backend policy (DESIGN.md §Kernels).

Every adapter hot-spot computation is a `KernelOp` keyed by
``(op, method, backend)``:

    op      — "deltaw" (dense ΔW materialization), "factored_apply"
              (y += x @ ΔW without ΔW), "bank_apply" (row-batched factored
              apply for the serving adapter bank), "paged_attention"
              (block-table decode attention for the paged KV cache)
    method  — the `AdapterMethod.name` that owns the math. Model-side ops
              (paged_attention) are owned by a non-adapter shim object with
              the same `name`/`kernel_ops()` surface
              (kernels/paged_attention.OWNER) — the registry only needs
              those two attributes
    backend — "pallas" (compiled TPU), "interpret" (Pallas interpret mode),
              "einsum" (pure-jnp reference)

Methods declare their implementations via `AdapterMethod.kernel_ops()`
(core/adapter.py); declarations are collected **lazily on first dispatch**
(`ensure_method`), never at import — the adapter and kernel packages import
each other's modules and eager registration would race the partially
initialized module namespaces.

Backend selection replaces the old ad-hoc `_use_pallas` string dispatch with
a capability model: each op declares `platforms`, an int32 phase bound
(`max_dim`), and an optional config predicate (`requires`); `resolve_op`
walks the requested policy's candidate chain and returns the first op whose
`supports()` passes. The einsum reference is the terminal candidate: `auto`
takes it off-TPU and, on TPU, only where the Pallas op's constraints reject
the site (vocab-sized grids past the int32 phase bound, non-Fourier bases);
"interpret" degrades the same way. An explicit "pallas" request never
degrades: where a registered Pallas op cannot run, resolution raises
`KernelUnavailableError` (ops with no Pallas registration at all keep their
einsum math).

`KernelPolicy` is the build-time snapshot: `Model.__post_init__` resolves
every targeted (site, op) pair once, raises (pallas) or warns (interpret)
when an explicitly requested backend cannot be honoured, and renders the
outcome via `explain()`.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple

OPS = ("deltaw", "factored_apply", "bank_apply", "paged_attention")
BACKENDS = ("pallas", "interpret", "einsum")

# candidate chain per requested policy; first supported op wins. "interpret"
# is debug-only: never auto-selected, and degrades to the einsum reference
# when the accelerated op's constraints fail; an explicit "pallas" whose
# registered op fails its constraints raises instead (resolve_op).
CANDIDATES: Dict[str, Tuple[str, ...]] = {
    "auto": ("pallas", "einsum"),
    "pallas": ("pallas", "einsum"),
    "interpret": ("interpret", "einsum"),
    "einsum": ("einsum",),
}


class KernelUnavailableError(KeyError):
    """No registered backend for (op, method) satisfies the constraints."""


@dataclass(frozen=True)
class KernelOp:
    """One backend implementation of one op for one adapter method.

    fn signatures (all return float32; the dispatch site casts):
        deltaw:          fn(trainable, aux, d1, d2, peft) -> (stack?, d1, d2)
        factored_apply:  fn(x, trainable, aux, d1, d2, peft) -> (..., d2)
        bank_apply:      fn(x, trainable, aux, d1, d2, peft) -> (B, ..., d2)

    Constraints: `platforms` (None = any jax backend), `max_dim` (largest
    d1/d2 whose integer phase reduction stays exact in int32 — includes the
    kernel's block padding, see DESIGN.md §Kernels), `requires` (predicate on
    the PEFTConfig, e.g. FourierFT's Pallas path needs basis == "fourier").

    `caps` is the kernel's machine-checkable capability metadata (the
    module-level `CAPS` dict of the implementing kernel module): block
    sizes, phase kind, scratch shapes — everything `repro.analysis`'s
    kernel-capability verifier needs to RE-DERIVE `max_dim` and the VMEM
    footprint instead of trusting the declaration (DESIGN.md §Analysis).
    None means "nothing to verify" (einsum references, XLA-op backends).

    Dispatch sites call the op itself, not `fn`: `__call__` runs `fn` under
    the name scope `{op}.{method}` (e.g. `bank_apply.fourierft`,
    `paged_attention.attention`), so every op the registry dispatches
    carries one stable name in the compiled program's op metadata
    (`op_name`), whichever backend resolved.
    """
    op: str
    method: str
    backend: str
    fn: Callable
    platforms: Optional[Tuple[str, ...]] = None
    max_dim: Optional[int] = None
    requires: Optional[Callable] = None
    note: str = ""
    caps: Optional[Dict] = None

    def __call__(self, *args, **kwargs):
        import jax
        with jax.named_scope(f"{self.op}.{self.method}"):
            return self.fn(*args, **kwargs)

    def supports(self, d1: int, d2: int, peft=None,
                 platform: Optional[str] = None) -> Tuple[bool, str]:
        """-> (ok, reason-if-not). `peft=None` skips config predicates."""
        if self.platforms is not None and platform not in self.platforms:
            return False, f"platform {platform!r} not in {self.platforms}"
        if self.max_dim is not None and max(d1, d2) > self.max_dim:
            return False, (f"dim {max(d1, d2)} over int32 phase bound "
                           f"{self.max_dim}")
        if self.requires is not None and peft is not None \
                and not self.requires(peft):
            return False, "config constraint (requires)"
        return True, ""


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_OPS: Dict[Tuple[str, str, str], KernelOp] = {}
_ENSURED: set = set()


def register_kernel_op(op: KernelOp) -> KernelOp:
    if op.op not in OPS:
        raise ValueError(f"unknown kernel op {op.op!r}; one of {OPS}")
    if op.backend not in BACKENDS:
        raise ValueError(f"unknown backend {op.backend!r}; one of {BACKENDS}")
    key = (op.op, op.method, op.backend)
    if key in _OPS:
        raise ValueError(f"kernel op {key} already registered")
    _OPS[key] = op
    return op


def _method_obj(method):
    """Accept an AdapterMethod instance or its registry name (resolved
    lazily — api.py must not import core.adapter at module level)."""
    if isinstance(method, str):
        from repro.core import adapter as adapter_api
        return adapter_api.resolve(method)
    return method


def ensure_method(method) -> None:
    """Collect `method.kernel_ops()` into the registry, once per method."""
    m = _method_obj(method)
    if m.name in _ENSURED:
        return
    _ENSURED.add(m.name)
    registered = []
    try:
        for op in m.kernel_ops():
            register_kernel_op(op)
            registered.append((op.op, op.method, op.backend))
    except BaseException:
        # roll back the partial pass entirely, so a retry after a transient
        # failure re-registers cleanly instead of hitting "already registered"
        for key in registered:
            _OPS.pop(key, None)
        _ENSURED.discard(m.name)
        raise


def lookup(op: str, method, backend: str) -> Optional[KernelOp]:
    m = _method_obj(method)
    ensure_method(m)
    return _OPS.get((op, m.name, backend))


def ops_for(method) -> Tuple[str, ...]:
    """Op names the method has any backend registered for."""
    m = _method_obj(method)
    ensure_method(m)
    return tuple(o for o in OPS
                 if any((o, m.name, b) in _OPS for b in BACKENDS))


def backends_for(op: str, method) -> Tuple[str, ...]:
    m = _method_obj(method)
    ensure_method(m)
    return tuple(b for b in BACKENDS if (op, m.name, b) in _OPS)


def all_ops() -> Tuple[KernelOp, ...]:
    """Every registered KernelOp, with every known owner's declarations
    collected first: all registered adapter methods plus the model-side
    paged-attention owner shim. This is the enumeration surface of
    `repro.analysis`'s kernel-capability verifier."""
    from repro.core import adapter as adapter_api
    for name in adapter_api.registered_methods():
        ensure_method(name)
    from repro.kernels import paged_attention
    ensure_method(paged_attention.OWNER)
    return tuple(_OPS[k] for k in sorted(_OPS))


def _platform() -> str:
    import jax
    return jax.default_backend()


def requested_backend(peft) -> str:
    return getattr(peft, "kernel_backend", None) or "auto"


def resolve_op(op: str, method, peft=None, d1: int = 0, d2: int = 0, *,
               backend: Optional[str] = None, platform: Optional[str] = None,
               missing_ok: bool = False) -> Optional[KernelOp]:
    """First registered op along the requested policy's candidate chain whose
    constraints pass. `backend` overrides `peft.kernel_backend`."""
    m = _method_obj(method)
    ensure_method(m)
    requested = backend or requested_backend(peft)
    if requested not in CANDIDATES:
        raise ValueError(f"unknown kernel backend {requested!r}; one of "
                         f"{sorted(CANDIDATES)}")
    platform = platform or _platform()
    for b in CANDIDATES[requested]:
        cand = _OPS.get((op, m.name, b))
        if cand is None:
            continue
        ok, why = cand.supports(d1, d2, peft, platform)
        if ok:
            return cand
        if requested == "pallas" and b == "pallas":
            raise KernelUnavailableError(
                f"kernel_backend='pallas' requested for ({op!r}, {m.name!r}) "
                f"at {d1}x{d2} on {platform}, but the pallas op cannot run "
                f"there: {why}")
    if missing_ok:
        return None
    raise KernelUnavailableError(
        f"no kernel op for ({op!r}, {m.name!r}) under backend={requested!r} "
        f"on {platform}; registered backends: {backends_for(op, m)}")


# ---------------------------------------------------------------------------
# Policy: per-model resolution snapshot
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Resolution:
    site: str
    d1: int
    d2: int
    op: str
    backend: str          # "" when nothing resolved (validate() rejects)
    note: str = ""


@dataclass(frozen=True)
class KernelPolicy:
    """Backend choice for every targeted (site, op) pair of one model,
    resolved once at model build (models/registry.py)."""
    method: str
    requested: str
    platform: str
    resolutions: Tuple[Resolution, ...] = ()

    @classmethod
    def build(cls, method, sites: Sequence, peft,
              platform: Optional[str] = None,
              attention: Optional[Tuple[int, int]] = None) -> "KernelPolicy":
        """`attention=(n_heads, head_dim)` adds the model-side
        `paged_attention` op (site "attention") for models that serve
        from the paged KV cache."""
        m = _method_obj(method)
        ensure_method(m)
        requested = requested_backend(peft)
        if requested not in CANDIDATES:
            raise ValueError(f"unknown kernel backend {requested!r}; one of "
                             f"{sorted(CANDIDATES)}")
        platform = platform or _platform()
        first = CANDIDATES[requested][0]

        def resolve(owner, site, d1, d2, op):
            chosen = resolve_op(op, owner, peft, d1, d2, platform=platform,
                                missing_ok=True)
            note = ""
            if chosen is None or chosen.backend != first:
                cand = _OPS.get((op, owner.name, first))
                why = (f"no {first} op registered" if cand is None
                       else cand.supports(d1, d2, peft, platform)[1])
                note = f"{first} unavailable: {why}"
            return Resolution(site, d1, d2, op,
                              chosen.backend if chosen else "", note)

        res = []
        if getattr(m, "has_site_params", True):
            targets = getattr(peft, "target_modules", ())
            for s in sites:
                if s.name.split("/")[-1] not in targets:
                    continue
                for op in ops_for(m):
                    res.append(resolve(m, s.name, s.d_in, s.d_out, op))
        if attention is not None:
            from repro.kernels import paged_attention
            ensure_method(paged_attention.OWNER)
            res.append(resolve(paged_attention.OWNER, "attention",
                               *attention, "paged_attention"))
        policy = cls(m.name, requested, platform, tuple(res))
        if requested == "interpret":
            # (an unhonourable explicit "pallas" already raised in
            # resolve_op.) Warn only where an op for the requested backend
            # EXISTS but its constraints rejected it — ops with no
            # accelerated registration (einsum-only math) fall through
            missed = sorted({f"{r.op}@{r.site}" for r in res
                             if r.backend != requested
                             and any((r.op, o, requested) in _OPS
                                     for o in (m.name, "attention"))})
            if missed:
                warnings.warn(
                    f"kernel_backend={requested!r} requested but unavailable "
                    f"for {missed} on {platform} — resolved to the fallback "
                    "chain (see Model.explain_kernels())", UserWarning,
                    stacklevel=3)
        return policy

    def backend_for(self, site: str, op: str) -> Optional[str]:
        for r in self.resolutions:
            if r.site == site and r.op == op:
                return r.backend or None
        return None

    def validate(self) -> "KernelPolicy":
        """Fail fast (pre-jit) on (site, op) pairs with no usable backend."""
        dead = [f"{r.op}@{r.site}" for r in self.resolutions if not r.backend]
        if dead:
            raise KernelUnavailableError(
                f"method {self.method!r}: no backend resolved for {dead} "
                f"under kernel_backend={self.requested!r} on {self.platform}")
        return self

    def explain(self) -> str:
        """Human-readable per-site resolution report (examples print this)."""
        head = (f"kernel policy: method={self.method} "
                f"requested={self.requested} platform={self.platform}")
        if not any(r.op != "paged_attention" for r in self.resolutions):
            head += "\n  (no registered kernel ops for this method)"
        lines = [head]
        for r in self.resolutions:
            line = (f"  {r.site} ({r.d1}x{r.d2}) {r.op} -> "
                    f"{r.backend or 'UNRESOLVED'}")
            if r.note:
                line += f"  [{r.note}]"
            lines.append(line)
        return "\n".join(lines)
