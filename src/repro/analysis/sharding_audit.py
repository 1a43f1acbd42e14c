"""Sharding-coverage audit (DESIGN.md §Analysis, §Sharding).

Placement is name-keyed, and any leaf matching no named decision silently
replicates (params) or rides the generic batch fall-through (caches/
batches). That fall-through is how the mamba2/hybrid families initially
shipped with undecided placements: the engine never errored, it just
replicated whatever it didn't recognize. This pass makes the decision
explicit: it asks the rule table, via `dist/plan.RulesSource.decision
(section, path, shape)`, which named rule placed each leaf, and flags a
leaf that no rule decided.

Coverage spans every tree serving and training place:

- param/state trees for every registered arch × a representative set of
  adapter methods ("state" section);
- decode caches — dense per-slot AND the paged page-pool — plus the serve
  batch leaves (block tables, adapter slot rows, scratch pages) and
  adapter-bank row stacks ("cache"/"batch"/"state" sections), so a searched
  plan can't silently leave a serving leaf unplaced.

The fix for a finding is to add the leaf name to the matching table in
dist/sharding.py (or ship a plan entry for it), making the placement a
decision instead of an accident.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from repro.analysis.report import Finding

# one method per distinct adapter-param leaf set: fourier/dct share "c"
# (+ spectral aux), lora has lora_a/lora_b, circulant has kernel+b1/b2,
# bitfit has delta_b — together they exercise every adapter leaf name.
DEFAULT_METHODS = ("fourierft", "dct", "lora", "circulant", "bitfit")

# serve-coverage geometry (shapes only — nothing materializes)
_SERVE_SLOTS = 4
_SERVE_LEN = 64
_PAGE_SIZE = 8
_N_PAGES = 16
_BANK_K = 2


def _iter_leaves(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _iter_leaves(v, path + (str(k),))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _iter_leaves(v, path + (str(i),))
    else:
        yield "/".join(path), tuple(getattr(tree, "shape", ()))


def _default_source():
    from repro.dist import plan as plan_mod
    return plan_mod.RulesSource()


def audit_tree(tree, label: str, section: str = "state",
               source=None) -> List[Finding]:
    """Flag every leaf of a (shape) tree that the rules left undecided —
    the silent fall-through instead of a named decision."""
    if source is None:
        source = _default_source()
    out: List[Finding] = []
    seen = set()
    for path, shape in _iter_leaves(tree):
        name = path.split("/")[-1]
        if source.decision(section, path, shape) is not None or name in seen:
            continue
        seen.add(name)                 # one finding per leaf NAME per tree
        out.append(Finding(
            "sharding", "uncovered", f"{label}/{name}",
            f"{section} leaf {path!r} (shape {shape}) has no placement "
            "decision from the sharding rules — it falls through "
            "undecided; add the name to a dist/sharding.py table"))
    return out


def _serve_trees(model):
    """(tree, label-suffix, section) triples for the serving surfaces:
    dense + paged decode caches, the decode batch (incl. block table and
    scratch pages), and adapter-bank row stacks."""
    import jax
    import jax.numpy as jnp
    out = []
    slot_cache = bool(model.supports_slot_cache)
    try:
        dense = jax.eval_shape(lambda: model.init_cache(
            _SERVE_SLOTS, _SERVE_LEN, per_slot=slot_cache))
        out.append((dense, "cache", "cache"))
    except Exception:
        pass
    if slot_cache:
        try:
            paged = jax.eval_shape(lambda: model.init_cache(
                _SERVE_SLOTS, _SERVE_LEN, paged=True,
                page_size=_PAGE_SIZE, n_pages=_N_PAGES))
            out.append((paged, "paged-cache", "cache"))
        except Exception:
            pass
    i32 = jnp.int32
    pages_per_seq = _SERVE_LEN // _PAGE_SIZE
    batch = {
        "tokens": jax.ShapeDtypeStruct((_SERVE_SLOTS, 1), i32),
        "block_table": jax.ShapeDtypeStruct((_SERVE_SLOTS, pages_per_seq),
                                            i32),
        "adapter_slots": jax.ShapeDtypeStruct((_SERVE_SLOTS,), i32),
        "true_len": jax.ShapeDtypeStruct((_SERVE_SLOTS,), i32),
        "prefix_len": jax.ShapeDtypeStruct((), i32),
        "slot": jax.ShapeDtypeStruct((), i32),
        "scratch_pages": jax.ShapeDtypeStruct((_SERVE_SLOTS,), i32),
    }
    out.append((batch, "serve-batch", "batch"))
    # adapter-bank rows: the peft site leaves with the (K+1,) bank-row dim
    # prepended — name-keyed placement must still cover them
    peft_tree = model.init_shapes().get("peft")
    if peft_tree:
        bank = {
            path: jax.ShapeDtypeStruct((_BANK_K + 1,) + shape, jnp.float32)
            for path, shape in _iter_leaves(peft_tree)}
        out.append((bank, "bank-rows", "state"))
    return out


def run(methods: Tuple[str, ...] = DEFAULT_METHODS,
        archs: Optional[Tuple[str, ...]] = None,
        source=None) -> List[Finding]:
    """Audit every registered arch's param tree, plus the serving surfaces
    (caches/batch/bank) on the first arch. The adapter-method sweep runs on
    the first arch only — adapter leaf names don't vary per family, and
    eval_shape per combination isn't free. `source` defaults to the rules."""
    from repro.models import registry
    if source is None:
        source = _default_source()
    out: List[Finding] = []
    first = serve_pick = None
    for arch, method, model in registry.analysis_models(
            methods=(methods[0],), archs=archs):
        first = first or (arch, model)
        # the serve surfaces (paged cache, block tables) need the slot-cache
        # families — audit them on the first arch that has one
        if serve_pick is None and bool(model.supports_slot_cache):
            serve_pick = (arch, model)
        out += audit_tree(model.init_shapes(), f"{arch}[{method}]",
                          source=source)
    if first is not None and len(methods) > 1:
        for arch, method, model in registry.analysis_models(
                methods=methods[1:], archs=(first[0],)):
            out += audit_tree(model.init_shapes(), f"{arch}[{method}]",
                              source=source)
    if first is not None:
        arch, model = serve_pick or first
        for tree, suffix, section in _serve_trees(model):
            out += audit_tree(tree, f"{arch}[{suffix}]", section=section,
                              source=source)
    return out
