"""Placement source (DESIGN.md §Sharding): the `dist/sharding.py` rule table
behind the small object interface its callers hold.

    RulesSource.state_specs / cache_specs / batch_specs
        — the rule table's own `sharding.state_specs` etc.;
    RulesSource.decision(section, path, shape)
        — which named rule covered a leaf (None = silent fall-through),
          what `analysis/sharding_audit` audits.

`resolve(arg, ...)` returns the rules for None, "" or "rules" and refuses
anything else: the rule table is the only placement.
"""
from __future__ import annotations

from typing import Optional, Tuple

from repro.dist import sharding as shd


class RulesSource:
    """The hand-written rule table; it delegates to the module functions."""

    kind = "rules"

    def state_specs(self, tree, mesh, cfg, fsdp: bool = False):
        return shd.state_specs(tree, mesh, cfg, fsdp=fsdp)

    def cache_specs(self, cache, mesh, cfg, shape):
        return shd.cache_specs(cache, mesh, cfg, shape)

    def batch_specs(self, batch, mesh, shape):
        return shd.batch_specs(batch, mesh, shape)

    def decision(self, section: str, path: str,
                 shape: Tuple[int, ...]) -> Optional[str]:
        """Which named rule covers this leaf (None = nobody placed it; the
        audit flags those)."""
        if section == "state":
            return shd.rule_kind(path, shape)
        if section == "cache":
            return shd.cache_rule_kind(path, shape)
        if section == "batch":
            return shd.batch_rule_kind(path, shape)
        raise ValueError(f"unknown section {section!r}")


def resolve(arg: Optional[str], *, model=None, mesh=None, shape=None,
            workload: Optional[str] = None) -> RulesSource:
    """The placement source for `arg`: the rules for None, "" or "rules".
    The keywords are accepted and ignored; the rules need none of them."""
    del model, mesh, shape, workload
    if arg in (None, "", "rules"):
        return RulesSource()
    raise ValueError(f"unknown sharding plan {arg!r}: the only placement "
                     "is 'rules'")
