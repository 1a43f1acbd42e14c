"""Serving engine: merged-adapter deployment (the paper's zero-inference-
latency property), prefill + batched greedy decode over slotted requests,
and a multi-tenant **adapter bank** (DESIGN.md §Adapter API).

`merge_for_serving` folds every mergeable ΔW into the base weights once —
after that the serving graph is byte-identical to the unadapted model's.
Sites that cannot merge stay factored and KEEP THEIR TRUE METHOD (the zamba2
shared-block per-application adapters; any method whose `mergeable` flag is
off).

`AdapterBank` holds K resident factored adapters over one base: per method
group the trainable leaves live in (K+1, L, …) arrays whose last row is a
reserved all-zero row. `Request.adapter_id` selects a resident row; the
jitted prefill/decode graphs gather per-request rows once per call and apply
them with the method's `bank_apply` — no per-request merge, no recompile
when residents change (array values change, shapes don't). Heterogeneous
methods batch together because every request gathers a row from every
method's bank and the factored contribution is linear in the trainables
(zero row ⇒ exactly zero). LRU load/evict against adapter-only checkpoints
(checkpoint/adapters.py) gives thousands-of-tenants serving at n·(2+L)
numbers of storage per tenant — the paper's economics, end to end.

The Engine itself batches in lockstep (generate / generate_requests); the
continuous-batching runtime over the same model/params/bank — arrival
scheduling, per-slot budgets over one persistent cache, slot recycling,
in-flight prefill — lives in repro.serve.scheduler (DESIGN.md §Scheduler).
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import PEFTConfig, ShapeConfig
from repro.core import adapter as adapter_api
from repro.models.registry import (
    Model, add_time_dim, build, resolve_default_targets,
)


def merge_for_serving(model: Model, params: Dict) -> Tuple[Model, Dict]:
    """Fold every mergeable layer-stack ΔW into the base. Leftover adapters
    (non-`layers/` sites such as the zamba2 shared block, or methods with
    `mergeable=False`) stay factored under their TRUE method — the rebuilt
    model keeps the original PEFTConfig whenever anything is left over.

    ΔW materialization runs through the method's `merge_site`, i.e. the
    kernel registry (DESIGN.md §Kernels): on TPU the compiled Pallas deltaw
    kernels do the folding; `model.explain_kernels()` reports the choice."""
    peft = model.peft
    method = model.method
    if not method.has_site_params or not params.get("peft"):
        return model, params
    base = dict(params["base"])
    layers = dict(base["layers"])
    leftover = {}
    site_by_name = {s.name: s for s in model.sites}
    for name, ad in params["peft"].items():
        if not name.startswith("layers/") or not method.mergeable:
            leftover[name] = ad      # e.g. zamba2 shared per-app adapters
            continue
        key = name.split("/")[-1]
        method.merge_site(layers, key, ad, site_by_name[name], peft)
    base["layers"] = layers
    merged_model = build(model.cfg,
                         peft if leftover else peft.replace(method="none"),
                         remat=model.remat)
    return merged_model, {"base": base, "peft": leftover}


@dataclass
class Request:
    prompt: jax.Array                  # (S,) int32
    max_new: int = 16
    adapter_id: Optional[str] = None   # resident AdapterBank tenant (or base)
    out: Optional[List[int]] = None
    priority: str = "batch"            # serve/tiering class: interactive |
                                       # batch | best_effort


class BankFullError(RuntimeError):
    """Raised by AdapterBank.load when the bank is at capacity and every
    resident tenant is pinned (in use by a live request) — the caller must
    defer the load until a pinned tenant's requests drain."""


class AdapterBank:
    """K resident factored adapters over one base model.

    `profiles` maps method name -> PEFTConfig: one bank group per method the
    deployment serves (all tenants of a group share frozen aux — entries /
    bases are keyed by method + entry seed, enforced at load). Rows:

        params[m]["sites"][site][leaf]  (K+1, L, ...)   trainable, zero-init
        params[m]["aux"][site][leaf]    shared frozen aux (entries, b1/b2)

    Row K is the reserved zero row: requests that don't use method m gather
    it and contribute exactly zero (linearity contract, core/adapter.py).
    Slots are global across groups — loading a tenant zeroes its slot row in
    every group, then writes its own method's leaves. Eviction is LRU.
    """

    def __init__(self, model: Model, profiles: Dict[str, PEFTConfig],
                 capacity: int, checkpoint_dir: Optional[str] = None):
        if capacity < 1:
            raise ValueError("AdapterBank needs capacity >= 1")
        self.capacity = capacity
        self.zero_row = capacity
        self.checkpoint_dir = checkpoint_dir
        self._cfg = model.cfg
        self.profiles: Dict[str, PEFTConfig] = {}
        self._bank_sites: Dict[str, List] = {}
        self.params: Dict[str, Dict] = {}
        for mname, prof in profiles.items():
            method = adapter_api.resolve(mname)
            if not method.has_site_params:
                raise ValueError(f"method {mname!r} has no adapter state")
            if prof.method != mname:
                prof = prof.replace(method=mname)
            prof = resolve_default_targets(prof, model.cfg)
            sites = [s for s in model.sites
                     if s.name.startswith("layers/")
                     and s.name.split("/")[-1] in prof.target_modules]
            if not sites:
                raise ValueError(f"profile {mname!r} targets no bank-eligible "
                                 f"(layers/*) site of {model.cfg.name}")
            self.profiles[mname] = prof
            self._bank_sites[mname] = sites
            group = {"sites": {}, "aux": {}}
            for site in sites:
                ad = method.init_site(jax.random.PRNGKey(0), site, prof)
                trainable = set(method.trainable_leaves(prof))
                group["sites"][site.name] = {
                    k: jnp.zeros((capacity + 1,) + v.shape, v.dtype)
                    for k, v in ad.items() if k in trainable}
                aux = {k: v for k, v in ad.items() if k not in trainable}
                if aux:
                    group["aux"][site.name] = aux
            self.params[mname] = group
        # adapter_id -> (method name, slot); insertion order = LRU order
        self._resident: "OrderedDict[str, Tuple[str, int]]" = OrderedDict()
        self._free = list(range(capacity))
        # optional HostAdapterTier (serve/tiering): when set, evicted rows
        # spill to pinned host arrays and reload without a checkpoint read
        self.host_tier = None

    # ---- residency --------------------------------------------------------
    @property
    def resident_ids(self) -> Tuple[str, ...]:
        return tuple(self._resident)

    # config fields with no effect on the served math — everything NOT listed
    # here must match the group profile (fail closed: a future method knob is
    # compared by default, not silently ignored). kernel_backend only selects
    # which registered implementation computes identical math (DESIGN.md
    # §Kernels); use_pallas is its deprecated alias (always None post-shim).
    _PROFILE_IRRELEVANT = ("strategy", "kernel_backend", "use_pallas",
                           "train_head", "param_dtype")

    def _profile_key(self, peft: PEFTConfig) -> tuple:
        d = dataclasses.asdict(peft)
        for k in self._PROFILE_IRRELEVANT:
            d.pop(k)
        return tuple(sorted(d.items()))

    def _snapshot_to_host(self, adapter_id: str, mname: str,
                          slot: int) -> None:
        """Spill one tenant's trainable rows to the host tier before the
        slot is cleared. The slices are handed over with the D2H copy
        dispatched asynchronously — the tier materializes them at its next
        settle(), overlapping the copy with whatever the device runs next.
        Must read the rows BEFORE `_clear_group_slot` zeroes them."""
        if self.host_tier is None:
            return
        group = self.params[mname]
        tree = {}
        for site, leaves in group["sites"].items():
            slices = {}
            for leaf, v in leaves.items():
                row = v[slot]
                row.copy_to_host_async()
                slices[leaf] = row
            tree[site] = slices
        self.host_tier.put(adapter_id, mname, tree)

    def _clear_group_slot(self, mname: str, slot: int) -> None:
        """Zero one slot row in one method group. Only the occupant's own
        group can hold non-zero rows (loads write exactly one group; freed
        slots are cleared on evict), so clearing stays O(one group), not
        O(whole bank), under LRU churn."""
        group = self.params[mname]
        for site, leaves in group["sites"].items():
            group["sites"][site] = {
                k: v.at[slot].set(jnp.zeros(v.shape[1:], v.dtype))
                for k, v in leaves.items()}

    def load(self, adapter_id: str, adapters: Dict, peft: PEFTConfig,
             pinned: Sequence[str] = ()) -> int:
        """Make `adapter_id` resident (LRU-evicting if full). `adapters` is a
        {site: {leaf: array}} tree — trainable leaves are written into the
        slot row; any frozen leaves present are validated against the group's
        shared aux (one bank group = one entry seed).

        pinned: tenant ids that must NOT be evicted (live requests are
        gathering their rows mid-stream — evicting one would zero the row
        under a decoding batch). The LRU victim is the least-recently-used
        UNPINNED resident; if every resident is pinned, BankFullError."""
        if peft.method not in self.profiles:
            raise KeyError(f"no bank group for method {peft.method!r}; "
                           f"groups: {sorted(self.profiles)}")
        prof = self.profiles[peft.method]
        peft = resolve_default_targets(peft, self._cfg)
        if self._profile_key(peft) != self._profile_key(prof):
            raise ValueError(
                f"adapter {adapter_id!r} config {self._profile_key(peft)} "
                f"does not match bank group {self._profile_key(prof)}")
        method = adapter_api.resolve(peft.method)
        group = self.params[peft.method]
        known = {s.name for s in self._bank_sites[peft.method]}
        stray = set(adapters) - known
        if stray:
            raise ValueError(
                f"adapter {adapter_id!r} carries sites {sorted(stray)} "
                f"outside the bank group's {sorted(known)} — serving it "
                "would silently drop them")
        # validate EVERYTHING before touching bank state: a failed load must
        # not leak a slot or wipe the tenant it would have evicted
        trainable = set(method.trainable_leaves(prof))
        writes = []
        for site in self._bank_sites[peft.method]:
            ad = adapters.get(site.name)
            if ad is None:
                continue                       # stays zero at this site
            missing = trainable - set(ad)
            if missing:                        # fail closed: a partial site
                raise ValueError(              # would silently serve wrong
                    f"{adapter_id!r} {site.name} is missing trainable "
                    f"leaves {sorted(missing)}")
            for leaf, v in ad.items():
                if leaf in trainable:
                    rows = group["sites"][site.name][leaf]
                    if v.shape != rows.shape[1:]:
                        raise ValueError(
                            f"{adapter_id!r} {site.name}/{leaf}: shape "
                            f"{v.shape} != bank row {rows.shape[1:]}")
                    writes.append((site.name, leaf, v))
                else:
                    shared = group["aux"].get(site.name, {}).get(leaf)
                    if shared is None or not np.array_equal(
                            np.asarray(v), np.asarray(shared)):
                        raise ValueError(
                            f"{adapter_id!r} frozen leaf {site.name}/{leaf} "
                            "differs from the bank group's shared aux "
                            "(adapters in one group must share entry seed)")
        if adapter_id in self._resident:
            prev_m, slot = self._resident.pop(adapter_id)
            self._clear_group_slot(prev_m, slot)
        elif self._free:
            slot = self._free.pop(0)           # zero by construction
        else:
            victim = next((a for a in self._resident if a not in pinned),
                          None)                # LRU order, skipping pinned
            if victim is None:
                raise BankFullError(
                    f"bank is full ({self.capacity} slots) and every "
                    f"resident tenant is pinned; cannot admit "
                    f"{adapter_id!r} until a pinned tenant drains")
            prev_m, slot = self._resident.pop(victim)
            self._snapshot_to_host(victim, prev_m, slot)
            self._clear_group_slot(prev_m, slot)
        for site_name, leaf, v in writes:
            rows = group["sites"][site_name][leaf]
            group["sites"][site_name][leaf] = \
                rows.at[slot].set(v.astype(rows.dtype))
        self._resident[adapter_id] = (peft.method, slot)
        if self.host_tier is not None:
            # any successful load supersedes a host copy (it would serve
            # stale rows if the tenant re-trained); eviction re-spills
            self.host_tier.drop(adapter_id)
        return slot

    def load_from_checkpoint(self, adapter_id: str,
                             directory: Optional[str] = None,
                             pinned: Sequence[str] = ()) -> int:
        """LRU reload path: import an adapter-only export (trainables + config
        manifest) and make it resident."""
        from repro.checkpoint import adapters as adapter_ckpt
        directory = directory or self.checkpoint_dir
        if directory is None:
            raise ValueError("no checkpoint directory configured")
        tree, peft = adapter_ckpt.import_adapter(directory, adapter_id)
        return self.load(adapter_id, tree, peft, pinned=pinned)

    def evict(self, adapter_id: str) -> None:
        mname, slot = self._resident.pop(adapter_id)
        self._snapshot_to_host(adapter_id, mname, slot)
        self._clear_group_slot(mname, slot)
        self._free.append(slot)

    def load_from_host(self, adapter_id: str,
                       pinned: Sequence[str] = ()) -> Optional[int]:
        """Make `adapter_id` resident from the host tier (serve/tiering),
        or return None on a host miss — the caller then falls back to
        `load_from_checkpoint`. Goes through `load()` so every validation
        (profile match, shapes, pinned-victim selection) applies to host
        reloads exactly as to checkpoint loads."""
        if self.host_tier is None:
            return None
        hit = self.host_tier.get(adapter_id)
        if hit is None:
            return None
        method, tree = hit
        return self.load(adapter_id, tree, self.profiles[method],
                         pinned=pinned)

    def touch(self, adapter_id: str) -> None:
        self._resident.move_to_end(adapter_id)

    def slot_rows(self, adapter_ids: Sequence[Optional[str]],
                  batch: int) -> Dict[str, jax.Array]:
        """Per-method gather rows for a batch: requests without an adapter —
        or using a different method — point at the reserved zero row."""
        if len(adapter_ids) > batch:
            raise ValueError(f"{len(adapter_ids)} adapter_ids for a "
                             f"{batch}-slot batch")
        missing = {a for a in adapter_ids
                   if a is not None and a not in self._resident}
        if missing:     # validate before touching: failed calls leave LRU as-is
            raise KeyError(f"adapters {sorted(missing)} are not resident; "
                           f"call load()/load_from_checkpoint() first")
        rows = {m: np.full((batch,), self.zero_row, np.int32)
                for m in self.profiles}
        for i, aid in enumerate(adapter_ids):
            if aid is None:
                continue
            mname, slot = self._resident[aid]
            rows[mname][i] = slot
            self.touch(aid)
        return {m: jnp.asarray(v) for m, v in rows.items()}


class Engine:
    """Slot-based batched greedy decoding (tests/examples scale).

    `mesh`: optional jax Mesh — merged params are placed per the dist
    sharding rules (TP over `model`, replicated over batch axes) and the KV
    cache per `cache_specs`, so the jitted prefill/decode graphs compile
    SPMD-partitioned instead of replicated.

    `bank`: optional AdapterBank — enables per-request `adapter_id`s; the
    bank's resident rows enter the jitted graphs as `params["bank"]` and the
    per-request gather indices as `batch["adapter_slots"]`, so residency
    changes never recompile."""

    def __init__(self, model: Model, params: Dict, batch_slots: int,
                 max_len: int, merge: bool = True, mesh=None,
                 bank: Optional[AdapterBank] = None, plan=None):
        if merge:
            # under the mesh, the Pallas ΔW kernels run per shard
            # (kernels/ops.py); without one they run as is
            with (jax.set_mesh(mesh) if mesh is not None
                  else contextlib.nullcontext()):
                model, params = merge_for_serving(model, params)
        self.bank = bank
        if bank is not None:
            # fresh Model facade: never mutate the caller's (merge may have
            # returned the input model unchanged, and it may be shared)
            model = dataclasses.replace(model,
                                        bank_profiles=dict(bank.profiles))
        self.mesh = mesh
        # plan: a dist.plan.RulesSource; None places by the rules
        from repro.dist import plan as plan_mod
        self.plan_source = plan if plan is not None else plan_mod.RulesSource()
        if mesh is not None:
            from repro.dist import sharding as shd
            specs = self.plan_source.state_specs(params, mesh, model.cfg,
                                                 False)
            params = jax.device_put(params, shd.named(params, specs, mesh))
        self.model, self.params = model, params
        self.batch = batch_slots
        self.max_len = max_len
        self._decode = jax.jit(model.decode_step)
        # one compiled graph per prompt length (padded batches share it)
        self._prefill = jax.jit(model.prefill)

    def commit_tokens(self, arr) -> jax.Array:
        """Place a host-built token array the way the jitted graphs hand
        theirs back: committed replicated over the engine mesh. A host-
        seeded step otherwise arrives UNcommitted while every device-fed
        step arrives with a NamedSharding — two jit signatures for one
        shape, which the recompile audit (repro.analysis) rightly flags."""
        arr = jnp.asarray(arr, jnp.int32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            arr = jax.device_put(arr, NamedSharding(self.mesh,
                                                    PartitionSpec()))
        return arr

    def _fresh_cache(self, per_slot: bool = False, paged: bool = False,
                     page_size: int = 16, n_pages: Optional[int] = None):
        cache = self.model.init_cache(self.batch, self.max_len,
                                      dtype=jnp.dtype(self.model.cfg.dtype),
                                      per_slot=per_slot, paged=paged,
                                      page_size=page_size, n_pages=n_pages)
        if self.mesh is not None:
            from repro.dist import sharding as shd
            shape = ShapeConfig("serve", self.max_len, self.batch, "decode")
            specs = self.plan_source.cache_specs(cache, self.mesh,
                                                 self.model.cfg, shape)
            cache = jax.device_put(cache, shd.named(cache, specs, self.mesh))
        return cache

    def _batch_extra(self, adapter_ids: Optional[Sequence[Optional[str]]]):
        """(params incl. bank rows, per-call batch extras) for one call's
        per-request adapter ids, None-padded to the engine's slot count.
        Shared by generate/generate_requests and the continuous scheduler
        so the three paths cannot diverge on bank wiring."""
        B = self.batch
        params = self.params
        extra: Dict = {}
        if self.bank is not None:
            ids = list(adapter_ids or [])
            ids += [None] * (B - len(ids))
            extra["adapter_slots"] = self.bank.slot_rows(ids, B)
            params = {**params, "bank": self.bank.params}
        elif adapter_ids is not None and any(a is not None for a in adapter_ids):
            raise ValueError("adapter_ids given but the engine has no bank")
        return params, extra

    def generate(self, prompts: List[jax.Array], max_new: int = 16,
                 stepwise_prefill: bool = False,
                 adapter_ids: Optional[Sequence[Optional[str]]] = None):
        """Greedy-decode a batch of equal-priority prompts (padded to the
        longest; padded prefill keeps every slot's KV cache consistent).

        adapter_ids: per-prompt AdapterBank tenant (None = bare base); the
        whole heterogeneous batch runs through ONE jitted graph.

        stepwise_prefill: legacy token-by-token teacher-forced prefill
        (reference path for the equivalence test; S decode dispatches)."""
        if not prompts:
            raise ValueError("generate() needs at least one prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if any(int(p.shape[0]) < 1 for p in prompts):
            raise ValueError("generate() got an empty (length-0) prompt")
        assert len(prompts) <= self.batch
        if adapter_ids is not None and len(adapter_ids) != len(prompts):
            # fail closed: a silently None-padded tail would serve those
            # prompts unadapted under the caller's nose
            raise ValueError(f"{len(adapter_ids)} adapter_ids for "
                             f"{len(prompts)} prompts")
        B = self.batch
        params, extra = self._batch_extra(adapter_ids)
        plen = max(int(p.shape[0]) for p in prompts)
        # same bound as the continuous scheduler (slots.py invariant: the
        # last generated token is never written — the deepest cache read is
        # plen + max_new - 1); the lockstep batch pads to the longest prompt
        if plen + max_new - 1 > self.max_len:
            raise ValueError(
                f"prompt ({plen}) + max_new ({max_new}) needs "
                f"{plen + max_new - 1} cache positions, exceeding "
                f"max_len ({self.max_len})")
        toks = jnp.zeros((B, plen) + prompts[0].shape[1:], jnp.int32)
        for i, p in enumerate(prompts):
            toks = toks.at[i, :p.shape[0]].set(p)
        cache = self._fresh_cache()
        if stepwise_prefill:
            last = None
            for t in range(plen):
                last, cache = self._decode(params, cache,
                                           {"tokens": toks[:, t:t + 1],
                                            **extra})
        else:
            last, cache = self._prefill(params, cache,
                                        {"tokens": toks, **extra})
        outs = [last]
        cur = add_time_dim(last)
        for _ in range(max_new - 1):
            nxt, cache = self._decode(params, cache,
                                      {"tokens": cur, **extra})
            outs.append(nxt)
            cur = add_time_dim(nxt)
        gen = jnp.stack(outs, axis=1)                     # (B, max_new, ...)
        return [gen[i] for i in range(len(prompts))]

    def generate_requests(self, requests: List[Request],
                          eos_id: Optional[int] = None):
        """Request-object front end: FCFS lockstep chunks of `batch_slots`
        heterogeneous-adapter requests (any count — chunks run serially).

        Per-request completion (budget exhausted, or `eos_id` emitted) is
        tracked through the scheduler's SlotManager — the same shared logic
        the continuous runtime uses — so a finished request stops
        contributing tokens, and the chunk's decode loop exits as soon as
        EVERY slot is done instead of always paying max(r.max_new) steps.
        Lockstep chunks cannot recycle a freed slot mid-flight; for that
        (plus arrival scheduling and in-flight prefill) use
        repro.serve.scheduler.ContinuousScheduler."""
        if not requests:
            return requests
        for r in requests:
            if r.max_new < 1:
                raise ValueError(f"request max_new must be >= 1, "
                                 f"got {r.max_new}")
            if int(r.prompt.shape[0]) < 1:
                raise ValueError("request with an empty (length-0) prompt")
        # validate every chunk's capacity bound UP FRONT (chunking is a
        # deterministic slice): an infeasible late request must fail before
        # any earlier chunk runs and mutates its requests' .out
        for at in range(0, len(requests), self.batch):
            chunk = requests[at:at + self.batch]
            plen = max(int(r.prompt.shape[0]) for r in chunk)
            worst = max(r.max_new for r in chunk)
            # per-chunk feasibility: every slot pads to the chunk's longest
            # prompt and decodes until its longest budget — same
            # `plen + max_new - 1 <= max_len` bound as generate() and the
            # continuous scheduler (slots.py invariant)
            if plen + worst - 1 > self.max_len:
                raise ValueError(
                    f"lockstep chunk at {at}: prompt ({plen}) + max_new "
                    f"({worst}) needs {plen + worst - 1} cache positions, "
                    f"exceeding max_len ({self.max_len})")
        for at in range(0, len(requests), self.batch):
            self._lockstep_chunk(requests[at:at + self.batch], eos_id)
        return requests

    def _lockstep_chunk(self, chunk: List[Request],
                        eos_id: Optional[int]) -> None:
        # lazy: scheduler.queue imports Request from this module
        from repro.serve.scheduler.slots import SlotManager
        params, extra = self._batch_extra([r.adapter_id for r in chunk])
        B = self.batch
        plen = max(int(r.prompt.shape[0]) for r in chunk)
        toks = jnp.zeros((B, plen) + chunk[0].prompt.shape[1:], jnp.int32)
        for i, r in enumerate(chunk):
            toks = toks.at[i, :r.prompt.shape[0]].set(r.prompt)
        last, cache = self._prefill(params, self._fresh_cache(),
                                    {"tokens": toks, **extra})
        sm = SlotManager(len(chunk), eos_id=eos_id)
        for i, r in enumerate(chunk):
            sm.acquire(i, budget=r.max_new, adapter_id=r.adapter_id)
        taken = [0] * len(chunk)
        history = []

        def note(tokens):
            history.append(tokens)
            # EOS needs token VALUES on the host (one sync per step);
            # budget-only completion stays async — dispatches pipeline.
            arr = np.asarray(tokens) if eos_id is not None else None
            for i in list(sm.active_slots()):
                taken[i] += 1
                tok = int(np.asarray(arr[i]).reshape(-1)[0]) \
                    if arr is not None else None
                if sm.note_token(i, tok):
                    sm.release(i)

        note(last)
        cur = add_time_dim(last)
        while sm.any_active():
            nxt, cache = self._decode(params, cache,
                                      {"tokens": cur, **extra})
            note(nxt)
            cur = add_time_dim(nxt)
        # the chunk's single drain point  # repro: allow(host-sync)
        gen = np.asarray(jnp.stack(history, axis=1))    # (B, T, ...)
        for i, r in enumerate(chunk):
            r.out = [int(t) for t in gen[i, :taken[i]].reshape(-1)]
