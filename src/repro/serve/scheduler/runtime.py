"""Continuous-batching runtime over the slot Engine (DESIGN.md §Scheduler).

One persistent fixed-shape KV cache: by default a PAGED cache (DESIGN.md
§Paging) — K/V in a global pool of fixed-size pages, each slot mapping its
logical positions onto pages through a block-table row, with page-aligned
prompt prefixes reused across requests (same tenant / bare base) so the
prime prefill computes only the unshared tail; `paged=False` keeps the
dense per-slot cache (`Model.init_cache(..., per_slot=True)`). Either way
every slot decodes at its own position/ragged kv_len, requests are
admitted into FREE slots the moment a slot, the tenant's bank row, AND (if
paged) the request's worst-case page count are available, and a slot is
recycled — its pages freed — the very step its request completes.
In-flight prefill primes a single slot while the other slots keep
decoding. All steady-state shapes are fixed: the decode graph NEVER
recompiles as requests come and go (the block table is a same-shape array
per call); prefill/splice compile once per pow2 prompt bucket.

Admission is adapter-bank-aware: a request's tenant is touched when
resident, loaded via `load_from_checkpoint` when not, with the tenants of
live slots pinned against LRU eviction (evicting one would zero the bank
row under a decoding batch). A request whose tenant cannot be made
resident right now waits, without head-of-line blocking the rest of the
queue.

Outputs are EXACT per request — bit-identical (fp32) to
`Engine.generate` run one request at a time: the prime prefill computes the
prompt at its true positions (`true_len` logits gather), pad-tail KV rows
are never readable (per-slot kv_len), and every decode einsum is
row-parallel.

Two throughput paths sit on top of the plain per-step decode loop:

- **Speculative decoding** (`drafter=`, DESIGN.md §Speculation): a
  `serve.spec.Drafter` proposes k tokens per slot; ONE `verify_step`
  forward (windowed paged_attention, q_len = k+1) scores all of them, the
  host accepts the longest greedy-consistent prefix per slot (EOS and
  budget clamp inside the window), and `advance_pos` commits per-slot
  deltas — rejection is position bookkeeping, never data movement, and the
  fixed (n_slots, k+1) verify shape never recompiles.
- **Buffered EOS detection**: the plain loop no longer syncs on every
  step's tokens. Decode feeds its own device output back as the next
  step's input; emitted tokens buffer on device and drain in one transfer
  when a budget completion is due (host-known, so budget-only traffic
  keeps its exact step timing), when the async per-slot EOS done-flag
  comes back set, or every `eos_sync_every` steps — so EOS-enabled decode
  no longer blocks on a host round-trip each step.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import pages_visited
from repro.serve.engine import BankFullError, Engine, Request
from repro.serve.paging import PagedKVCache, PrefixCache, PrimePlan
from repro.serve.scheduler.metrics import ServingMetrics, span
from repro.serve.scheduler.queue import RequestQueue, ScheduledRequest
from repro.serve.scheduler.slots import SlotManager
from repro.serve.tiering import (
    PRIORITIES, HostAdapterTier, HostPagePool, TieringConfig, VictimInfo,
    choose_mode, choose_victim, priority_rank,
)

Event = Tuple  # ("admit", rid, slot, t) | ("token", rid, tok, t)
               # | ("done", rid, toks, t) | ("preempt", rid, slot, t)
               # | ("resume", rid, slot, t)


@dataclass
class ResumeState:
    """How a preempted request comes back (queue.ScheduledRequest.resume):
    "swap" restores the host snapshot of its KV pages; "recompute"
    re-prefills prompt + everything already emitted. Either way the
    resumed stream is bit-identical to an unpreempted run (DESIGN.md
    §Tiering)."""
    mode: str


def _bucket(n: int, lo: int = 8) -> int:
    """Next power of two >= n (floored at `lo`): bounds prime-prefill
    compilations at log2(max_len) graphs under arbitrary prompt lengths."""
    b = lo
    while b < n:
        b <<= 1
    return b


class ContinuousScheduler:
    """Continuous-batching front end over an Engine's model/params/bank.

    eos_id:  optional stop token — a slot completes on emitting it (the
             token is included in the output). Detected from the buffered
             device-side done-flag (no per-step host round-trip); at most
             `eos_sync_every` decode steps run past an EOS before the
             drain discards the overshoot.
    policy:  RequestQueue admission order ("fcfs" | "resident_first").
    bucket:  pad prime prefills to pow2 prompt buckets (bounded compile
             count); False compiles per distinct prompt length instead.
    paged:   block-table page-pool cache with shared-prefix reuse
             (DESIGN.md §Paging; the default) vs the dense per-slot cache.
             Outputs are bit-identical (fp32) either way.
    page_size / n_pages: paged-cache geometry (n_pages defaults to the
             zero-sharing worst case plus prefix-cache headroom, see
             serve/paging.PagedKVCache).
    drafter: optional `serve.spec.Drafter` — switches the decode loop to
             draft-then-verify speculative decoding (DESIGN.md
             §Speculation). Greedy outputs stay token-identical to the
             non-speculative path; `metrics` grows acceptance counters.
    eos_sync_every: max decode steps between token drains when eos_id is
             set and no completion is otherwise due (bounds both EOS
             detection latency and wasted overshoot steps).
    tiering: optional `serve.tiering.TieringConfig` — priority classes,
             preempt-and-resume under page/bank pressure, and host-RAM
             tiers for KV pages and adapter-bank rows (DESIGN.md
             §Tiering). Preemption needs the paged cache; the adapter
             host tier works either way. Resumed streams are bit-
             identical (fp32) to an unpreempted run.

    Streaming API: `events()` yields ("admit", rid, slot, t),
    ("token", rid, token, t) and ("done", rid, tokens, t) tuples as they
    happen; `serve(requests, arrivals)` replays a trace and returns the
    requests with `.out` filled. `metrics` accumulates TTFT / occupancy /
    tokens-per-s (ServingMetrics).
    """

    def __init__(self, engine: Engine, eos_id: Optional[int] = None,
                 policy: str = "fcfs", bucket: bool = True,
                 paged: bool = True, page_size: int = 16,
                 n_pages: Optional[int] = None, drafter=None,
                 eos_sync_every: int = 4,
                 tiering: Optional[TieringConfig] = None):
        if not engine.model.supports_slot_cache:
            raise NotImplementedError(
                f"{engine.model.cfg.name}: continuous batching needs the "
                "per-slot cache path (token-input transformer families)")
        self.engine = engine
        self.model = engine.model
        self.bank = engine.bank
        self.n_slots = engine.batch
        self.max_len = engine.max_len
        self.eos_id = eos_id
        self.bucket = bucket
        self.queue = RequestQueue(policy)
        self.pager: Optional[PagedKVCache] = None
        if paged:
            self.pager = PagedKVCache(self.n_slots, self.max_len,
                                      page_size=page_size, n_pages=n_pages)
        self.slots = SlotManager(self.n_slots, eos_id=eos_id,
                                 on_release=self._release_pages)
        self.metrics = ServingMetrics()
        self.t = 0.0                           # decode-step clock
        self._decode = engine._decode          # shared jit: per-slot trace
        self._prefill = engine._prefill        # shared jit: (1, P) traces
        self._write = jax.jit(self.model.write_slot, donate_argnums=(0,))
        self._reset = jax.jit(self.model.reset_slots, donate_argnums=(0,))
        if paged:
            self.cache = engine._fresh_cache(
                paged=True, page_size=self.pager.page_size,
                n_pages=self.pager.n_pages)
            self._prefill_paged = jax.jit(self.model.prefill_paged,
                                          donate_argnums=(1,))
            self._copy_page = jax.jit(self.model.copy_page,
                                      donate_argnums=(0,))
        else:
            self.cache = engine._fresh_cache(per_slot=True)
        self._cache_dtype = jnp.dtype(self.model.cfg.dtype)
        self._sr: List[Optional[ScheduledRequest]] = [None] * self.n_slots
        self._plans: Dict[int, PrimePlan] = {}
        self._prefix_keys: Dict[int, list] = {}   # rid -> memoized hashes
        self._last = [0] * self.n_slots        # per-slot last token (host)
        self._outs: Dict[int, List[int]] = {}
        self._stale = set()                    # freed, not yet reset slots
        # buffered decode state (plain loop): device token feedback plus
        # not-yet-drained step outputs and the async EOS done-flag
        self.eos_sync_every = max(1, int(eos_sync_every))
        self._pending: List[Tuple] = []        # (t, nt_dev, [(slot, sr)..])
        self._toks_dev = None                  # (B, 1) next-step tokens
        self._flag_dev = None                  # (B,) device done-flags
        self._flag_prev = None                 # last flag snapshot in flight
        if eos_id is not None:
            eid = int(eos_id)
            self._or_eos = jax.jit(lambda f, nt: f | (nt == eid))
        # speculative decoding (DESIGN.md §Speculation)
        self.drafter = drafter
        if drafter is not None:
            self._verify = jax.jit(self.model.verify_step)
            drafter.bind(self)
        self._advance = jax.jit(self.model.advance_pos,
                                donate_argnums=(0,))
        if paged:
            # verify-window overflow writes route to the slot's reserved
            # scratch page (paging.py: scratch page of slot i is page i)
            self._scratch_pages = jnp.arange(self.n_slots, dtype=jnp.int32)
        # tiering (DESIGN.md §Tiering): host pools + page-pool move ops
        self.tiering = tiering
        self.host_kv: Optional[HostPagePool] = None
        self.host_adapters: Optional[HostAdapterTier] = None
        self._no_admit: set = set()        # preempted this admission round
        if tiering is not None and self.pager is not None:
            # page-pool spill/fill: model-agnostic ops on the paged cache
            # dict (pk/pv pools + per-slot pos) — gathers are dispatched
            # BEFORE the pages are freed/donated, so stream order reads
            # the old contents; fills donate the cache like every other
            # cache-threading jit here
            self._spill_pages = jax.jit(
                lambda c, idx: (jnp.take(c["pk"], idx, axis=1),
                                jnp.take(c["pv"], idx, axis=1)))
            self._fill_pages = jax.jit(
                lambda c, k, v, idx: {**c,
                                      "pk": c["pk"].at[:, idx].set(k),
                                      "pv": c["pv"].at[:, idx].set(v)},
                donate_argnums=(0,))
            self._set_pos = jax.jit(
                lambda c, slot, pos: {**c,
                                      "pos": c["pos"].at[slot].set(pos)},
                donate_argnums=(0,))
            if tiering.host_kv_pages > 0:
                self.host_kv = HostPagePool(tiering.host_kv_pages)
                # touch (not just probe): planned fill keys become MRU so
                # the same plan's demotions displace older entries first
                self.pager.host_has = self.host_kv.touch_prefix
                self.pager.prefix_cache.on_evict = self._demote_prefix_page
        if tiering is not None and tiering.host_adapter_slots > 0 \
                and self.bank is not None:
            # the closure reads self.metrics at call time, so the counter
            # survives reset_metrics() swapping the metrics object
            self.host_adapters = HostAdapterTier(
                tiering.host_adapter_slots,
                on_spill=lambda: self.metrics.on_adapter_spill())
            self.bank.host_tier = self.host_adapters

    # ---- submission -------------------------------------------------------
    def submit(self, request: Request, arrival: float = 0.0) -> int:
        """Queue a request; `arrival` is on the decode-step clock (traffic
        replay). Returns the request id used in events/metrics."""
        if request.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {request.max_new}")
        S = int(request.prompt.shape[0])
        if S < 1:
            raise ValueError("empty (length-0) prompt")
        # cache-position bound (slots.py invariant: the LAST generated token
        # is never written, so the final position used is S + max_new - 2
        # and the deepest read is kv_len = S + max_new - 1). The previous
        # `S + max_new > max_len` guard rejected feasible requests by one
        # token — a request may generate through exactly max_len positions.
        if S + request.max_new - 1 > self.max_len:
            raise ValueError(
                f"prompt ({S}) + max_new ({request.max_new}) needs "
                f"{S + request.max_new - 1} cache positions, exceeding the "
                f"persistent cache's max_len ({self.max_len})")
        if request.adapter_id is not None and self.bank is None:
            raise ValueError("request has an adapter_id but the engine "
                             "has no bank")
        if request.priority not in PRIORITIES:
            raise ValueError(f"unknown priority {request.priority!r}; "
                             f"one of {PRIORITIES}")
        rid = self.queue.push(request, arrival)
        self.metrics.on_arrival(rid, float(arrival),
                                priority=request.priority)
        self.metrics.queue_depth = len(self.queue)
        return rid

    def reset_metrics(self) -> None:
        """Fresh per-run metrics AND a rewound decode-step clock for a new
        trace replay (compiled graphs stay warm). Only meaningful between
        drains — rewinding under live requests would corrupt their stamps.
        The monotonic cumulative counters (requests admitted/cancelled/…,
        ServingMetrics.COUNTERS) carry over: a /metrics scrape must never
        see them dip."""
        if self.slots.any_active() or len(self.queue):
            raise RuntimeError("reset_metrics with requests in flight")
        self.metrics = ServingMetrics(carry=self.metrics)
        self.t = 0.0

    # ---- admission --------------------------------------------------------
    def _ensure_resident(self, sr: ScheduledRequest) -> bool:
        """Make the request's tenant bank-resident (admission side effect).
        False = defer: the bank is full of pinned (live) tenants."""
        aid = sr.request.adapter_id
        if aid is None:
            return True
        if aid in self.bank.resident_ids:
            self.bank.touch(aid)
            return True
        pinned = [a for a in self.slots.adapter_ids() if a is not None]
        try:
            if self.host_adapters is not None:
                # host tier first: a hit skips the checkpoint read entirely
                if self.bank.load_from_host(aid, pinned=pinned) is not None:
                    self.metrics.on_adapter_host_hit()
                    return True
            self.bank.load_from_checkpoint(aid, pinned=pinned)
        except BankFullError:
            return False
        return True

    def _effective(self, sr: ScheduledRequest) -> Tuple[np.ndarray, int]:
        """(prompt, max_new) as the admission path sees them. A resumed
        request re-enters with prompt + everything already emitted as its
        effective prompt and only its remaining budget left — identical
        page totals and the exact slot invariants of an unpreempted run
        at the same point (DESIGN.md §Tiering)."""
        prompt = np.asarray(sr.request.prompt)
        if sr.resume is None:
            return prompt, sr.request.max_new
        done = self._outs[sr.rid]
        return (np.concatenate([prompt, np.asarray(done, np.int32)]),
                sr.request.max_new - len(done))

    def _try_admit(self, sr: ScheduledRequest) -> bool:
        """Admission callback for the queue: bank residency first, then (if
        paged) the page plan — matching the prefix cache and allocating the
        slot's worst-case pages up-front, so decode never allocates. False
        defers the request without head-of-line blocking the queue.

        Resumes ride the same path: a swap-resume allocates all its pages
        privately (`plan_resume` — the snapshot holds the exact KV); a
        recompute-resume plans its EFFECTIVE prompt through the ordinary
        prefix-matching admission, so it may share cached prefix pages
        ("recompute-from-prefix")."""
        if sr.rid in self._no_admit:
            return False       # just preempted: re-admitting it this round
                               # would thrash it against its preemptor
        if not self._ensure_resident(sr):
            return False
        if self.pager is None:
            return True
        prompt, max_new = self._effective(sr)
        if sr.resume is not None and sr.resume.mode == "swap":
            total = -(-(int(prompt.shape[0]) + max_new - 1)
                      // self.pager.page_size)
            plan = self.pager.plan_resume(self.slots.free_slots()[0], total)
            if plan is None:
                return False
            self._plans[sr.rid] = plan
            return True
        memo = self._prefix_keys.get(sr.rid)
        if memo is None:                     # hash + host-copy once;
            memo = (prompt, PrefixCache.chain_keys(  # deferred requests
                prompt, self.pager.page_size,        # are re-offered
                sr.request.adapter_id))              # every cycle
            self._prefix_keys[sr.rid] = memo
        prompt, keys = memo
        plan = self.pager.plan_admit(
            self.slots.free_slots()[0], prompt, max_new,
            adapter_id=sr.request.adapter_id, keys=keys)
        if plan is None:
            return False
        self._plans[sr.rid] = plan
        self._prefix_keys.pop(sr.rid, None)
        return True

    def _release_pages(self, slot: int, snapshot) -> None:
        """SlotManager release hook: a recycled slot frees its pages the
        same scheduler step its request completes."""
        if self.pager is not None:
            self.pager.release(slot)

    def _bucketed_prompt(self, tokens, n: int) -> Tuple[int, Dict]:
        """(padded length P, {tokens, true_len?}) for a batch-1 prefill:
        pow2-bucketed, clamped to max_len (the bucket of a near-max prompt
        can overshoot a non-pow2 cache), `true_len` present iff padded —
        the ONE place both prime flavors get their prefill shapes from."""
        P = min(_bucket(n), self.max_len) if self.bucket else n
        batch: Dict = {"tokens":
                       jnp.zeros((1, P), jnp.int32).at[0, :n].set(tokens)}
        if P != n:
            batch["true_len"] = jnp.full((1,), n, jnp.int32)
        return P, batch

    def _promote_fills(self, plan: PrimePlan, prompt) -> None:
        """Copy the plan's host-matched chunks back into their owned device
        pages before the prime (one batched H2D + scatter; padded rows land
        in the slot's scratch page). The entries stay host-resident — LRU
        ages them out.

        A fill can vanish between plan and promote: `plan_admit`'s own
        eviction demotes device prefix pages into the host pool, and when
        the pool is full those demotions displace its LRU entries — the
        planner touches its fill keys to MRU, but enough same-plan
        demotions can still reach them. The chain shares from the front,
        so everything past the first missing chunk is unusable: truncate
        the fills there and extend the tail back over the lost chunks —
        the prime recomputes them into the already-owned pages, keeping
        the stream exact at a recompute cost."""
        n = len(plan.fills)
        width = _bucket(n, lo=1)
        k = v = idx = None
        filled = 0
        for i, (c, key) in enumerate(plan.fills):
            hit = self.host_kv.get_prefix(key)
            if hit is None:
                self.metrics.on_kv_fill_degraded(n - i)
                plan.prefix_len = c * self.pager.page_size
                plan.tail = np.asarray(prompt)[plan.prefix_len:]
                del plan.fills[i:]
                break
            hk, hv = hit
            if k is None:
                k = np.zeros((hk.shape[0], width) + hk.shape[2:], hk.dtype)
                v = np.zeros_like(k)
                idx = np.full((width,), plan.scratch_page, np.int32)
            k[:, i], v[:, i] = hk[:, 0], hv[:, 0]
            idx[i] = plan.block_row[c]
            filled += 1
        if not filled:
            return
        self.cache = self._fill_pages(self.cache, jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray(idx))
        self.metrics.on_kv_fill(filled)
        self.metrics.on_prefix_host_hit(filled)

    def _prime(self, sr: ScheduledRequest, slot: int,
               prompt=None) -> int:
        """In-flight prefill: run the prompt through a batch-1 scratch
        prefill and splice its KV into `slot` of the live cache. Returns the
        first generated token. On the paged cache, only the UNSHARED TAIL of
        the prompt is computed (`Model.prefill_paged`): reused prefix pages
        enter the tail's attention through the block-table window, after the
        COW clone when the plan calls for one. `prompt` overrides the
        request's own (recompute-resume primes prompt + emitted)."""
        prompt = sr.request.prompt if prompt is None else prompt
        params = self.engine.params
        extra: Dict = {}
        if self.bank is not None:
            extra["adapter_slots"] = self.bank.slot_rows(
                [sr.request.adapter_id], 1)
            params = {**params, "bank": self.bank.params}
        with span("sched.prime", rid=sr.rid) as prime:
            if self.pager is not None:
                plan = self._plans.pop(sr.rid)
                if plan.cow is not None:
                    self.cache = self._copy_page(self.cache, *plan.cow)
                if plan.fills:
                    self._promote_fills(plan, prompt)
                P, batch = self._bucketed_prompt(jnp.asarray(plan.tail),
                                                 int(plan.tail.shape[0]))
                batch.update(block_table=jnp.asarray(plan.block_row[None]),
                             slot=jnp.int32(slot),
                             scratch_page=jnp.int32(plan.scratch_page),
                             **extra)
                if plan.prefix_len:
                    # warm prime: the attention window gathers only the
                    # pow2 bucket of the PREFIX pages (compile count stays
                    # log-bounded) — not the full pages_per_seq window,
                    # which would cost O(tail * max_len) at long max_len.
                    # Cold primes omit both keys and take the statically
                    # window-free graph.
                    ps = self.pager.page_size
                    wp = min(_bucket(-(-plan.prefix_len // ps), lo=1),
                             self.pager.pages_per_seq)
                    batch["window_table"] = jnp.asarray(
                        plan.block_row[None, :wp])
                    batch["prefix_len"] = jnp.int32(plan.prefix_len)
                nt, self.cache = self._prefill_paged(params, self.cache,
                                                     batch)
            else:
                S = int(prompt.shape[0])
                P, batch = self._bucketed_prompt(prompt, S)
                batch.update(extra)
                scratch = self.model.init_cache(1, P,
                                                dtype=self._cache_dtype)
                nt, scratch = self._prefill(params, scratch, batch)
                self.cache = self._write(
                    self.cache, {"k": scratch["k"], "v": scratch["v"]},
                    slot, S)
            prime.annotate(bucket=P)
            tok = int(np.asarray(nt).reshape(-1)[0])
            if self.pager is not None:
                # publish the prompt's chunks for future sharing only past
                # the host sync above (async dispatch errors surface
                # there) — a failed prime must not leave prefix-cache
                # entries pointing at never-filled pages
                self.pager.register_prompt(plan)
        self.metrics.on_prime(sr.rid, prime.seconds)
        return tok

    def _admit_ready(self) -> Iterator[Event]:
        self._no_admit = set()
        try:
            while len(self.queue):
                resident = self.bank.resident_ids if self.bank else ()
                sr = None
                if self.slots.free_slots():
                    sr = self.queue.pop_next(self.t, self._try_admit,
                                             resident=resident)
                if sr is not None:
                    with span("sched.admit", rid=sr.rid):
                        yield from self._admit_one(sr)
                    continue
                # blocked: no free slot, or every arrived request deferred
                # on pages/bank. Deferral was the only option pre-tiering;
                # with preemption on, evict a strictly-lower-class victim
                # for the head-of-policy-order candidate and retry.
                if (self.tiering is None or not self.tiering.preempt
                        or self.pager is None):
                    return
                cand = self.queue.peek_next(self.t, resident=resident)
                if cand is None or cand.rid in self._no_admit:
                    return
                evs = self._preempt_for(cand)
                if evs is None:
                    return
                yield from evs
                if not any(e[0] in ("preempt", "done") for e in evs):
                    return    # drained tokens only: nothing was freed, so
                              # retrying admission would spin
        finally:
            self._no_admit = set()

    def _admit_one(self, sr: ScheduledRequest) -> Iterator[Event]:
        """Acquire + prime one accepted request (fresh or resumed)."""
        resume = sr.resume
        prompt, max_new = self._effective(sr)
        plan = self._plans.get(sr.rid)
        slot = self.slots.acquire(sr.rid, budget=max_new,
                                  adapter_id=sr.request.adapter_id,
                                  prompt_len=int(prompt.shape[0]),
                                  slot=plan.slot if plan else None)
        self._sr[slot] = sr
        if resume is not None and resume.mode == "swap":
            # restore the snapshot: no prefill, no token — the slot picks
            # up exactly where the victim stopped (pos = S_eff - 1, next
            # input = the last emitted token), so the next decode emits
            # the same token an unpreempted run would have
            sr.resume = None
            plan = self._plans.pop(sr.rid)
            k, v, n_used = self.host_kv.pop_snapshot(sr.rid)
            idx = np.full((k.shape[1],), plan.scratch_page, np.int32)
            idx[:n_used] = plan.block_row[:n_used]
            self.cache = self._fill_pages(self.cache, jnp.asarray(k),
                                          jnp.asarray(v), jnp.asarray(idx))
            self.cache = self._set_pos(self.cache, jnp.int32(slot),
                                       jnp.int32(int(prompt.shape[0]) - 1))
            self.metrics.on_kv_fill(n_used)
            tok = self._outs[sr.rid][-1]
            self._last[slot] = tok
            if self._toks_dev is not None:
                self._toks_dev = self._toks_dev.at[slot, 0].set(tok)
            if self.drafter is not None:
                self.drafter.on_prime(slot, prompt[:-1], tok)
            self.metrics.on_resume(sr.rid, self.t)
            yield ("resume", sr.rid, slot, self.t)
            return
        if resume is not None:
            sr.resume = None
            self.metrics.on_resume(sr.rid, self.t)
        else:
            self.metrics.on_admit(sr.rid, self.t)
        tok = self._prime(sr, slot, prompt=prompt)
        if resume is None:
            self._outs[sr.rid] = [tok]
        else:
            # recompute-resume: the prime re-prefilled prompt + emitted
            # and produced the NEXT token of the stream
            self._outs[sr.rid].append(tok)
        self._last[slot] = tok
        if self._toks_dev is not None:
            # mid-buffer admission: in-flight slots' next tokens live
            # only on device, so splice the new slot's first token in
            # instead of rebuilding from the (stale) host view
            self._toks_dev = self._toks_dev.at[slot, 0].set(tok)
        if self.drafter is not None:
            self.drafter.on_prime(slot, np.asarray(prompt), tok)
        self.metrics.on_token(sr.rid, self.t)
        self.queue.note_usage(sr.request.adapter_id, 1)
        yield (("resume" if resume is not None else "admit"),
               sr.rid, slot, self.t)
        yield ("token", sr.rid, tok, self.t)
        if self.slots.note_token(slot, tok):
            yield self._finish(slot)

    def _demote_prefix_page(self, key: bytes, page: int) -> None:
        """PrefixCache on_evict hook: instead of dropping a cold prefix
        page, gather its KV (dispatched BEFORE the page returns to the
        free list — stream order reads the old contents even if a later
        prime reuses the page) and hand the in-flight copy to the host
        tier; `settle()` materializes it after the round's device work."""
        k, v = self._spill_pages(self.cache,
                                 jnp.full((1,), page, jnp.int32))
        k.copy_to_host_async()
        v.copy_to_host_async()
        if self.host_kv.put_prefix(key, k, v):
            self.metrics.on_kv_spill(1)

    def _preempt_for(self, cand: ScheduledRequest) -> Optional[List[Event]]:
        """Evict one strictly-lower-class victim slot so `cand` can admit
        (DESIGN.md §Tiering). Returns the events produced (the pre-evict
        drain may finish slots), or None when nothing is eligible. The
        victim's KV leaves by snapshot-to-host ("swap") or is dropped for
        re-prefill at resume ("recompute"), per the cost estimate; either
        way it re-enters the queue with its rid, arrival, and emitted
        tokens intact, and resumes bit-identical."""
        # drain first: the host view of emitted tokens must be current
        # before sizing/snapshotting a victim, and a buffered completion
        # may free a slot outright — in which case just retry admission
        # (a slot that was ALREADY free means the candidate is blocked on
        # pages/bank, and eviction below is still the right move)
        free_before = len(self.slots.free_slots())
        evs = list(self._drain())
        if len(self.slots.free_slots()) > free_before:
            return evs
        crank = priority_rank(cand.request.priority)
        occupants = []
        for slot in self.slots.active_slots():
            vsr = self._sr[slot]
            if vsr is None:
                continue
            st = self.slots.state(slot)
            occupants.append(VictimInfo(
                slot=slot,
                rank=priority_rank(vsr.request.priority),
                prompt_len=int(vsr.request.prompt.shape[0]),
                emitted=len(self._outs[vsr.rid]),
                # rows actually written: pos = prompt_len + taken - 1
                used_pages=-(-(st.prompt_len + st.taken - 1)
                             // self.pager.page_size)))
        victim = choose_victim(crank, occupants)
        if victim is None:
            return evs if evs else None
        vsr = self._sr[victim.slot]
        mode = choose_mode(self.tiering, victim, self.pager.page_size,
                           host_can_swap=self.host_kv is not None)
        if mode == "swap":
            # gather the victim's used pages (padded to a pow2 width with
            # its scratch page — harmless dirt both ways) and pin the
            # in-flight copy in the host pool; a pool too full of other
            # snapshots degrades to recompute, never to waiting
            n_used = victim.used_pages
            width = _bucket(n_used, lo=1)
            idx = np.full((width,), victim.slot, np.int32)
            idx[:n_used] = self.pager.block_tables[victim.slot][:n_used]
            k, v = self._spill_pages(self.cache, jnp.asarray(idx))
            k.copy_to_host_async()
            v.copy_to_host_async()
            if self.host_kv.put_snapshot(vsr.rid, k, v, n_used):
                self.metrics.on_kv_spill(n_used)
            else:
                mode = "recompute"
        vsr.resume = ResumeState(mode)
        self._sr[victim.slot] = None
        self._last[victim.slot] = 0
        self.slots.release(victim.slot)   # frees pages via on_release —
        self._stale.add(victim.slot)      # AFTER the spill gather above
        if self.drafter is not None:
            self.drafter.on_release(victim.slot)
        self._prefix_keys.pop(vsr.rid, None)   # resume re-hashes eff prompt
        self.metrics.on_preempt(vsr.rid, self.t, mode)
        self.queue.requeue(vsr)
        self._no_admit.add(vsr.rid)
        evs.append(("preempt", vsr.rid, victim.slot, self.t))
        return evs

    def _finish(self, slot: int, t: Optional[float] = None) -> Event:
        t = self.t if t is None else t
        sr = self._sr[slot]
        self._sr[slot] = None
        self._last[slot] = 0
        self.slots.release(slot)
        self._stale.add(slot)          # reset is batched into the next step
        if self.drafter is not None:
            self.drafter.on_release(slot)
        toks = self._outs.pop(sr.rid)
        sr.request.out = toks
        self.metrics.on_finish(sr.rid, t)
        return ("done", sr.rid, toks, t)

    # ---- cancellation ------------------------------------------------------
    def cancel(self, rid: int) -> bool:
        """Abort request `rid` wherever it is — the client-disconnect path
        (DESIGN.md §Gateway). A queued request is withdrawn; an ACTIVE one
        releases its slot THIS step: `SlotManager.release` fires the
        on_release hook (freeing the slot's KV pages), the tenant's bank
        row is unpinned the moment the slot leaves `slots.adapter_ids()`,
        and any not-yet-drained buffered tokens for the slot are discarded
        by the drain's occupancy check (the same mechanism that drops
        post-EOS overshoot). Returns True iff the request was found live;
        its `.out` holds the tokens emitted before the abort."""
        sr = self.queue.remove(rid)
        if sr is not None:       # still queued: never admitted, or waiting
            self._prefix_keys.pop(rid, None)   # to resume after preemption
            if self.host_kv is not None:
                self.host_kv.drop_snapshot(rid)
            sr.request.out = self._outs.pop(rid, [])
            self.metrics.on_cancel(rid, self.t)
            self.metrics.queue_depth = len(self.queue)
            return True
        for slot in self.slots.active_slots():
            sr = self._sr[slot]
            if sr is None or sr.rid != rid:
                continue
            self._sr[slot] = None              # buffered overshoot for this
            self._last[slot] = 0               # slot now drains to nowhere
            self.slots.release(slot)           # frees pages via on_release
            self._stale.add(slot)
            if self.drafter is not None:
                self.drafter.on_release(slot)
            sr.request.out = self._outs.pop(rid, [])
            if not self.slots.any_active():
                # nothing left to drain for: drop the buffered-decode state
                # now instead of carrying dead device work into the next
                # admission cycle
                self._pending.clear()
                self._flag_dev = None
                self._flag_prev = None
            self.metrics.on_cancel(rid, self.t)
            return True
        return False

    # ---- decode -----------------------------------------------------------
    def _flush_stale(self) -> None:
        """One batched reset for slots freed since the last step; slots that
        were already re-primed (write_slot set their position) drop out."""
        stale = self._stale & set(self.slots.free_slots())
        self._stale.clear()
        if stale:
            mask = np.zeros((self.n_slots,), bool)
            mask[list(stale)] = True
            self.cache = self._reset(self.cache, mask)

    def _batch_inputs(self) -> Tuple[Dict, Dict]:
        """(params, extra) for a full-batch decode/verify dispatch."""
        params, extra = self.engine.params, {}
        if self.pager is not None:
            extra["block_table"] = self.pager.block_table_device()
        if self.bank is not None:
            extra["adapter_slots"] = self.bank.slot_rows(
                self.slots.adapter_ids(), self.n_slots)
            params = {**params, "bank": self.bank.params}
        return params, extra

    def _kv_lens(self) -> np.ndarray:
        """Per-slot kv_len of the next decode or verify dispatch, from host
        state alone: an active slot sits at prompt_len + taken - 1 plus one
        per buffered step it occupied; a free slot sits at 0 (reset, and
        decode leaves it there). kv_len is the position + 1."""
        pos = np.zeros((self.n_slots,), np.int64)
        for slot in self.slots.active_slots():
            st = self.slots.state(slot)
            pos[slot] = st.prompt_len + st.taken - 1
        for _, _, occupants in self._pending:
            for slot, sr in occupants:
                if self._sr[slot] is sr:
                    pos[slot] += 1
        return pos + 1

    def _count_live_pages(self, window: int) -> int:
        """Pages the paged-attention kernel fetches and computes in each
        layer of this step's dispatch (`pages_visited`); recorded as a
        share of its (n_slots, pages_per_seq) grid."""
        pps = self.pager.pages_per_seq
        n = pages_visited(self._kv_lens(), window, self.pager.page_size, pps)
        self.metrics.on_attn_pages(n, self.n_slots * pps)
        return n

    def _min_budget_left(self) -> int:
        """Tokens until the EARLIEST budget completion among active slots,
        counted from the last drain — once the buffer holds that many
        steps, a completion is inside it and must be processed (so
        budget-only traffic drains at exactly its completion steps and
        keeps the unbuffered loop's scheduling timing)."""
        budgets = [self.slots.state(s).budget
                   for s in self.slots.active_slots()]
        return min(budgets) if budgets else 0

    def _decode_once(self) -> Iterator[Event]:
        self._flush_stale()
        active = self.slots.active_slots()
        params, extra = self._batch_inputs()
        if self._toks_dev is None:
            self._toks_dev = self.engine.commit_tokens(
                np.asarray(self._last, np.int32)[:, None])
        nt, self.cache = self._decode(params, self.cache,
                                      {"tokens": self._toks_dev, **extra})
        # feed the device output straight back as the next step's input —
        # the host never sees tokens until a drain
        self._toks_dev = nt[:, None]
        self.t += 1
        self.metrics.on_step(len(active), self.n_slots)
        self._pending.append((self.t, nt, [(s, self._sr[s]) for s in active]))
        sync = self._min_budget_left() <= len(self._pending)
        if self.eos_id is not None:
            if self._flag_dev is None:
                self._flag_dev = jnp.zeros((self.n_slots,), jnp.bool_)
            self._flag_dev = self._or_eos(self._flag_dev, nt)
            # the PREVIOUS flag snapshot has had a full decode dispatch to
            # come back (copy_to_host_async below) — reading it now is
            # effectively free, and one step of detection latency only
            # delays the drain, never correctness
            if self._flag_prev is not None \
                    and bool(np.asarray(self._flag_prev).any()):
                sync = True
            self._flag_dev.copy_to_host_async()
            self._flag_prev = self._flag_dev
            if len(self._pending) >= self.eos_sync_every:
                sync = True
        if sync:
            yield from self._drain()

    def _drain(self) -> Iterator[Event]:
        """Fetch every buffered step's tokens in ONE device transfer and
        replay them through the per-token accounting, stamped with their
        original step times. Slots that complete mid-buffer stop
        contributing from that step on (their later buffered tokens — the
        decode overshoot — are discarded, exactly what the unbuffered loop
        never generated; the device rows were dirt past their kv_len)."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        self._flag_dev = None
        self._flag_prev = None
        with span("sched.drain"):
            # THE drain: one transfer per buffer  # repro: allow(host-sync)
            arr = np.asarray(jnp.stack([nt for _, nt, _ in pending]))
            for i, (t, _, occupants) in enumerate(pending):
                for slot, sr in occupants:
                    if self._sr[slot] is not sr:   # finished earlier
                        continue
                    tok = int(arr[i, slot])
                    self._outs[sr.rid].append(tok)
                    self._last[slot] = tok
                    self.metrics.on_token(sr.rid, t)
                    self.queue.note_usage(sr.request.adapter_id, 1)
                    yield ("token", sr.rid, tok, t)
                    if self.slots.note_token(slot, tok):
                        yield self._finish(slot, t)

    # ---- speculative decode (DESIGN.md §Speculation) ----------------------
    def _spec_decode_once(self) -> Iterator[Event]:
        """One draft-then-verify step: the drafter proposes k tokens per
        slot, ONE `verify_step` forward scores the (n_slots, k+1) window,
        and each active slot accepts the longest prefix greedy decoding
        would have emitted — token j is kept iff draft j matched the
        model's own output after token j-1, with EOS and budget clamping
        anywhere inside the window. Accepted counts commit to the device
        `pos` via `advance_pos` (0 for FREE slots); rejected rows stay
        past kv_len as dirt the next window overwrites."""
        self._flush_stale()
        active = self.slots.active_slots()
        params, extra = self._batch_inputs()
        if self.pager is not None:
            extra["scratch_pages"] = self._scratch_pages
        k = self.drafter.k
        # drafters propose on device (SelfDrafter) or host (NGramDrafter);
        # the window assembles on device either way, and the host reads the
        # window AND the verify scores in ONE transfer after dispatch —
        # previously this synced twice per step (once on propose, once on
        # the scores)
        drafts = jnp.asarray(self.drafter.propose(), jnp.int32)
        last = jnp.asarray(np.asarray(self._last, np.int32))
        win_dev = jnp.concatenate([last[:, None], drafts], axis=1)
        out, self.cache = self._verify(params, self.cache,
                                       {"tokens": win_dev, **extra})
        self.t += 1
        self.metrics.on_step(len(active), self.n_slots)
        # the step's single intended sync point  # repro: allow(host-sync)
        wa = np.asarray(jnp.concatenate([win_dev, out], axis=1))
        win, arr = wa[:, :k + 1], wa[:, k + 1:]
        deltas = np.zeros((self.n_slots,), np.int32)
        for slot in active:
            sr = self._sr[slot]
            # greedy acceptance: token j is valid iff draft j matched the
            # model's own continuation after token j-1 (token 0 is the
            # mandatory next token — always valid)
            accepted = [int(arr[slot, 0])]
            for j in range(1, k + 1):
                if win[slot, j] != arr[slot, j - 1]:
                    break
                accepted.append(int(arr[slot, j]))
            n_emit, done = self.slots.note_window(slot, accepted)
            emitted = accepted[:n_emit]         # budget/EOS clamp
            for tok in emitted:
                self._outs[sr.rid].append(tok)
                self._last[slot] = tok
                self.metrics.on_token(sr.rid, self.t)
                self.queue.note_usage(sr.request.adapter_id, 1)
                yield ("token", sr.rid, tok, self.t)
            deltas[slot] = n_emit
            self.drafter.on_tokens(slot, emitted)
            self.metrics.on_spec(sr.rid, drafted=k, accepted=n_emit - 1,
                                 emitted=n_emit)
            if done:
                yield self._finish(slot)
        self.cache = self._advance(self.cache, jnp.asarray(deltas))

    # ---- static-analysis surface (repro.analysis, DESIGN.md §Analysis) ----
    def compiled_signatures(self) -> Dict[str, int]:
        """Compiled-signature count per jitted graph this scheduler
        dispatches (jit cache sizes — no tracing, safe anytime). Note the
        decode/prefill entries are the ENGINE's shared jits: a fresh Engine
        per scheduler keeps the counts attributable to this scheduler."""
        out = {"decode": int(self._decode._cache_size()),
               "reset": int(self._reset._cache_size()),
               "advance": int(self._advance._cache_size()),
               "write": int(self._write._cache_size())}
        if self.pager is not None:
            out["prefill_paged"] = int(self._prefill_paged._cache_size())
            out["copy_page"] = int(self._copy_page._cache_size())
        else:
            out["prefill"] = int(self._prefill._cache_size())
        if self.drafter is not None:
            out["verify"] = int(self._verify._cache_size())
        if self.eos_id is not None:
            out["or_eos"] = int(self._or_eos._cache_size())
        if self.tiering is not None and self.pager is not None:
            out["spill_pages"] = int(self._spill_pages._cache_size())
            out["fill_pages"] = int(self._fill_pages._cache_size())
            out["set_pos"] = int(self._set_pos._cache_size())
        return out

    def expected_compile_bounds(self) -> Dict[str, int]:
        """The compile-count CONTRACT the pow2 bucketing declares, keyed
        like `compiled_signatures()`. decode/verify run at one fixed
        (n_slots, ·) shape → exactly 1 graph regardless of churn; prime
        prefills compile per pow2 prompt bucket (× cold + pow2 prefix-
        window buckets when paged). With `bucket=False` prefill compiles
        per distinct prompt length — unbounded by design — so no prefill
        bound is declared and the analyzer skips it."""
        bounds = {"decode": 1, "reset": 1, "advance": 1}
        if self.drafter is not None:
            bounds["verify"] = 1
            # scalar rollback (drafter probe) + (B,) accept-commit deltas
            bounds["advance"] = 2
        if self.eos_id is not None:
            bounds["or_eos"] = 1
        if self.pager is not None:
            bounds["copy_page"] = 1
            bounds["write"] = 0            # dense-path graph, unused here
        if self.bucket:
            # pow2 buckets in [8, _bucket(max_len)]
            n_len = _bucket(self.max_len).bit_length() - 3
            if self.pager is not None:
                # pow2 warm prefix-window widths in [1, _bucket(pages)]
                wins = _bucket(self.pager.pages_per_seq, lo=1).bit_length()
                bounds["prefill_paged"] = n_len * (1 + wins)
            else:
                bounds["prefill"] = n_len
                bounds["write"] = n_len    # scratch k/v shape per bucket
        if self.tiering is not None and self.pager is not None:
            # spill/fill widths are pow2-bucketed in [1, _bucket(pages)]
            # regardless of the prompt-bucket flag (the widths come from
            # page counts, not prompt lengths)
            widths = _bucket(self.pager.pages_per_seq, lo=1).bit_length()
            bounds["spill_pages"] = widths
            bounds["fill_pages"] = widths
            bounds["set_pos"] = 1
        return bounds

    def resource_gauges(self) -> Dict[str, float]:
        """Occupancy gauges for the gateway's /metrics scrape (DESIGN.md
        §Tiering): bank residency, prefix-cache and page-pool fill, and
        host-tier occupancy when tiering is on."""
        out: Dict[str, float] = {}
        if self.bank is not None:
            out["bank_resident_adapters"] = float(len(self.bank.resident_ids))
        if self.pager is not None:
            out["prefix_cache_pages"] = float(len(self.pager.prefix_cache))
            out["kv_pages_free"] = float(self.pager.allocator.free_count())
        if self.host_kv is not None:
            out["host_kv_pages_used"] = float(self.host_kv.used_pages)
            out["host_kv_pages_capacity"] = float(self.host_kv.capacity_pages)
        if self.host_adapters is not None:
            out["host_adapter_rows"] = float(len(self.host_adapters))
            out["host_adapter_capacity"] = float(self.host_adapters.capacity)
        return out

    # ---- main loop --------------------------------------------------------
    def tick(self) -> List[Event]:
        """ONE scheduler round — admit every admissible arrived request,
        then (if anything is decoding) one decode/verify step — returning
        the round's events. Returns [] when there is nothing to do right
        now: the queue is empty or its head hasn't arrived yet (the round
        idle-skips the clock to the next arrival), or every arrived request
        is deferred on resources. Unlike `events()`, tick() never raises on
        an un-admittable backlog: under live traffic a later round can free
        what admission waits on (a disconnect cancels a slot, a drain
        unpins a tenant), so the async gateway pumps this from its own
        loop (serve/gateway/bridge.py) and decides idleness itself.

        Host spans (`metrics.span`): the round is `sched.tick`; inside it
        each admitted request's `sched.admit` holds its `sched.prime`, and
        the decode step's `sched.decode` holds any `sched.drain`. On the
        paged cache `sched.decode` carries `live_pages`: the pages the
        attention kernel visits per layer in that step."""
        with span("sched.tick"):
            evs: List[Event] = list(self._admit_ready())
            active = self.slots.active_slots()
            if active:
                with span("sched.decode", active=len(active)) as dec:
                    if self.pager is not None:
                        window = 1 if self.drafter is None \
                            else self.drafter.k + 1
                        dec.annotate(
                            live_pages=self._count_live_pages(window))
                    if self.drafter is not None:
                        evs.extend(self._spec_decode_once())
                    else:
                        evs.extend(self._decode_once())
            else:
                nxt = self.queue.next_arrival()
                if nxt is not None and nxt > self.t:
                    self.t = nxt           # idle: skip to the next arrival
            if self.host_kv is not None:
                # materialize the round's in-flight spills now that the
                # decode work is dispatched (the async D2H copies
                # overlapped it); holding them longer would pin their HBM
                # source buffers
                self.host_kv.settle()
            if self.host_adapters is not None:
                self.host_adapters.settle()
            self.metrics.queue_depth = len(self.queue)
        return evs

    def events(self) -> Iterator[Event]:
        """Drain the queue: admit -> decode -> recycle until no request is
        pending or in flight, yielding the event stream. Re-entrant across
        drains (the persistent cache and clock carry over), but only one
        events() iterator may be live at a time."""
        self.metrics.start()
        try:
            while len(self.queue) or self.slots.any_active():
                t_before = self.t
                evs = self.tick()
                yield from evs
                if not evs and not self.slots.any_active() \
                        and self.t == t_before and len(self.queue):
                    # no admission, no decode, no idle-skip progress, yet
                    # requests remain: a replay can never free what they
                    # wait on (live traffic can — see tick())
                    raise RuntimeError(
                        "scheduler stalled: arrived requests cannot be "
                        "admitted although every slot is free")
        finally:
            self.metrics.stop()

    def serve(self, requests: Sequence[Request],
              arrivals: Optional[Sequence[float]] = None) -> List[Request]:
        """Traffic replay: submit every request (arrivals on the decode-step
        clock, default all-at-0) and drain. Returns the requests with `.out`
        filled, in input order."""
        if arrivals is not None and len(arrivals) != len(requests):
            raise ValueError(f"{len(arrivals)} arrivals for "
                             f"{len(requests)} requests")
        for i, r in enumerate(requests):
            self.submit(r, arrivals[i] if arrivals is not None else 0.0)
        for _ in self.events():
            pass
        return list(requests)
