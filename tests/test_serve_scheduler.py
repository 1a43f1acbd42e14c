"""Continuous-batching scheduler (DESIGN.md §Scheduler): per-request
outputs bit-identical (fp32) to the serial engine under staggered arrivals
and heterogeneous adapters, slot recycling under churn, bank-aware
admission (live-tenant pinning, LRU eviction mid-stream), slot-lifecycle
invariants, and the Engine.generate_requests per-slot completion fix."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.checkpoint import adapters as adapter_ckpt
from repro.configs.base import PEFTConfig
from repro.core import adapter as adapter_api
from repro.core import peft as peft_mod
from repro.models import build
from repro.serve import (
    AdapterBank, BankFullError, ContinuousScheduler, Engine, Request,
)
from repro.serve.scheduler.slots import ACTIVE, FREE, SlotManager

TENANTS = ("tenant-fft", "tenant-lora")
METHODS = ("fourierft", "lora")


def _cfg(arch="yi-6b"):
    return C.reduced(C.get(arch)).replace(vocab=64, param_dtype="float32",
                                          dtype="float32")


def _profiles():
    return {
        "fourierft": PEFTConfig(method="fourierft", n=16, alpha=25.0,
                                param_dtype="float32"),
        "lora": PEFTConfig(method="lora", lora_r=2, param_dtype="float32"),
    }


def _base_model():
    model = build(_cfg(), PEFTConfig(method="none"))
    return model, model.init(jax.random.PRNGKey(0))


def _export_tenants(model, directory, tenant_ids=TENANTS, methods=METHODS):
    profiles = _profiles()
    for i, (tid, m) in enumerate(zip(tenant_ids, methods)):
        prof = profiles[m]
        tree = peft_mod.init_adapters(jax.random.PRNGKey(10 + i),
                                      model.sites, prof)
        tree = jax.tree.map(
            lambda x: x + 0.05 if jnp.issubdtype(x.dtype, jnp.floating)
            else x, tree)
        trainable = set(adapter_api.resolve(m).trainable_leaves(prof))
        tree = {s: {k: v for k, v in d.items() if k in trainable}
                for s, d in tree.items()}
        adapter_ckpt.export_adapter(str(directory), tid, tree, prof)
    return profiles


def _serial(engine, req):
    """Reference: the request alone through Engine.generate (exact
    per-request semantics — no foreign padding, own decode length)."""
    if req.adapter_id is not None and \
            req.adapter_id not in engine.bank.resident_ids:
        engine.bank.load_from_checkpoint(req.adapter_id)
    out = engine.generate([req.prompt], max_new=req.max_new,
                          adapter_ids=[req.adapter_id]
                          if engine.bank is not None else None)[0]
    return [int(t) for t in np.asarray(out).reshape(-1)]


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12], [3, 1, 4, 1, 5, 9],
           [2, 7, 1, 8], [6, 6, 6], [9, 8, 7, 6, 5, 4, 3], [5, 5]]


def _trace(max_news, adapter_ids=None):
    return [Request(prompt=jnp.array(PROMPTS[i % len(PROMPTS)], jnp.int32),
                    max_new=mn,
                    adapter_id=adapter_ids[i] if adapter_ids else None)
            for i, mn in enumerate(max_news)]


class TestExactness:
    def test_staggered_arrivals_bitwise_vs_serial(self):
        """Acceptance: continuous outputs == one-request-at-a-time engine,
        bit-identical at fp32, under staggered arrivals + mixed budgets."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=3, max_len=48)
        reqs = _trace([4, 7, 2, 5, 1, 6, 3, 8])
        sched = ContinuousScheduler(eng)
        sched.serve(reqs, arrivals=[0, 0, 1, 2, 3, 5, 8, 9])
        for r in reqs:
            assert r.out == _serial(eng, r)
        s = sched.metrics.summary()
        assert s["total_tokens"] == sum(len(r.out) for r in reqs)
        assert 0 < s["occupancy_mean"] <= 1

    def test_heterogeneous_adapters_bitwise(self, tmp_path):
        """Mixed tenants (two methods + bare base) in one continuous batch
        reproduce each request's serial outputs exactly."""
        model, params = _base_model()
        profiles = _export_tenants(model, tmp_path)
        bank = AdapterBank(model, profiles, capacity=4,
                           checkpoint_dir=str(tmp_path))
        eng = Engine(model, params, batch_slots=3, max_len=48, bank=bank)
        ids = ["tenant-fft", "tenant-lora", None, "tenant-fft",
               "tenant-lora", None]
        reqs = _trace([5, 3, 6, 2, 4, 3], adapter_ids=ids)
        ContinuousScheduler(eng).serve(reqs, arrivals=[0, 0, 0, 1, 3, 4])
        for r in reqs:
            assert r.out == _serial(eng, r)

    def test_exact_prime_matches_bucketed(self):
        """bucket=False (per-length prefill) and bucket=True (pow2 padded
        prefill + true_len gather) are the same math."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        a = _trace([4, 3, 5])
        ContinuousScheduler(eng, bucket=True).serve(a, [0, 1, 2])
        b = _trace([4, 3, 5])
        ContinuousScheduler(eng, bucket=False).serve(b, [0, 1, 2])
        assert [r.out for r in a] == [r.out for r in b]

    def test_bucket_clamped_to_non_pow2_max_len(self):
        """Regression: a near-max prompt whose pow2 bucket overshoots a
        non-pow2 max_len must clamp to max_len, not crash the splice."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        long_p = jnp.arange(40, dtype=jnp.int32) % 64
        reqs = [Request(prompt=long_p, max_new=5)]
        ContinuousScheduler(eng).serve(reqs)
        assert reqs[0].out == _serial(eng, reqs[0])

    def test_event_stream(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        sched = ContinuousScheduler(eng)
        rids = [sched.submit(r, t) for r, t in zip(_trace([3, 2]), (0, 1))]
        events = list(sched.events())
        kinds = [e[0] for e in events]
        assert kinds.count("admit") == 2 and kinds.count("done") == 2
        for rid, n in zip(rids, (3, 2)):
            toks = [e[2] for e in events if e[0] == "token" and e[1] == rid]
            done = next(e for e in events if e[0] == "done" and e[1] == rid)
            assert toks == done[2] and len(toks) == n

    def test_unsupported_family_raises(self):
        cfg = C.reduced(C.get("mamba2-2.7b")).replace(
            vocab=64, param_dtype="float32", dtype="float32")
        model = build(cfg, PEFTConfig(method="none"))
        eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                     batch_slots=2, max_len=32)
        with pytest.raises(NotImplementedError):
            ContinuousScheduler(eng)


class TestSlotLifecycle:
    def test_recycling_under_churn(self):
        """More requests than slots: freed slots are re-primed in flight and
        every request still matches the serial reference."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        reqs = _trace([3, 1, 4, 2, 5, 2, 3, 1, 2, 4])
        sched = ContinuousScheduler(eng)
        admits = []
        for r, t in zip(reqs, [0] * 10):
            sched.submit(r, t)
        for ev in sched.events():
            if ev[0] == "admit":
                admits.append(ev[2])
        assert all(r.out is not None for r in reqs)
        for r in reqs:
            assert r.out == _serial(eng, r)
        # both slots recycled repeatedly
        assert admits.count(0) >= 3 and admits.count(1) >= 3
        assert not sched.slots.any_active()

    def test_lru_eviction_mid_stream(self, tmp_path):
        """A non-resident tenant arriving against a full bank must wait for
        a pinned (live) tenant to drain, then evict it via LRU — and the
        still-running streams are unaffected."""
        model, params = _base_model()
        profiles = _export_tenants(
            model, tmp_path,
            tenant_ids=("t-a", "t-b", "t-c"),
            methods=("fourierft", "fourierft", "lora"))
        bank = AdapterBank(model, profiles, capacity=2,
                           checkpoint_dir=str(tmp_path))
        eng = Engine(model, params, batch_slots=3, max_len=48, bank=bank)
        reqs = _trace([8, 2, 3], adapter_ids=["t-a", "t-b", "t-c"])
        sched = ContinuousScheduler(eng)
        for r, t in zip(reqs, (0, 0, 1)):
            sched.submit(r, t)
        events = list(sched.events())
        admit_t = {e[1]: e[3] for e in events if e[0] == "admit"}
        done_t = {e[1]: e[3] for e in events if e[0] == "done"}
        # t-c could not be admitted at its arrival (bank full, both pinned):
        # it waited for t-b to finish
        assert admit_t[2] >= done_t[1]
        # t-b was evicted for t-c; the long-running t-a stayed resident
        assert "t-b" not in bank.resident_ids
        assert {"t-a", "t-c"} <= set(bank.resident_ids)
        for r in reqs:
            assert r.out == _serial(eng, r)

    def test_load_refuses_to_evict_pinned(self, tmp_path):
        model, _ = _base_model()
        profiles = _export_tenants(
            model, tmp_path, tenant_ids=("t-a", "t-b", "t-c"),
            methods=("fourierft", "fourierft", "fourierft"))
        bank = AdapterBank(model, profiles, capacity=2,
                           checkpoint_dir=str(tmp_path))
        bank.load_from_checkpoint("t-a")
        bank.load_from_checkpoint("t-b")
        with pytest.raises(BankFullError):
            bank.load_from_checkpoint("t-c", pinned=["t-a", "t-b"])
        assert set(bank.resident_ids) == {"t-a", "t-b"}  # load left no hole
        # unpinning one lets the LRU (t-a) go
        bank.load_from_checkpoint("t-c", pinned=["t-b"])
        assert set(bank.resident_ids) == {"t-b", "t-c"}


class TestSlotManagerInvariants:
    def _fuzz(self, ops):
        """Drive acquire/release/note against an external model of the
        assignment; any double assignment or phantom release must raise."""
        sm = SlotManager(4)
        assigned = {}                      # slot -> rid (external truth)
        next_rid = 0
        for op, slot in ops:
            if op == "acquire":
                if len(assigned) == len(sm):
                    with pytest.raises(RuntimeError):
                        sm.acquire(next_rid, budget=3)
                else:
                    got = sm.acquire(next_rid, budget=3)
                    assert got not in assigned          # never double-assign
                    assigned[got] = next_rid
                    next_rid += 1
            elif op == "release":
                if slot in assigned:
                    sm.release(slot)
                    del assigned[slot]
                else:
                    with pytest.raises(RuntimeError):
                        sm.release(slot)
            else:                          # note
                if slot in assigned:
                    if sm.note_token(slot):
                        sm.release(slot)
                        del assigned[slot]
                else:
                    with pytest.raises(RuntimeError):
                        sm.note_token(slot)
            assert set(sm.active_slots()) == set(assigned)
            assert set(sm.free_slots()) == \
                set(range(len(sm))) - set(assigned)

    @given(st.lists(st.tuples(st.sampled_from(["acquire", "release", "note"]),
                              st.integers(min_value=0, max_value=3)),
                    max_size=200))
    @settings(max_examples=50, deadline=None)
    def test_no_double_assignment_property(self, ops):
        self._fuzz(ops)

    def test_no_double_assignment_fuzz(self):
        """Deterministic mirror of the property test (runs when hypothesis
        is absent)."""
        rng = random.Random(0)
        for _ in range(20):
            ops = [(rng.choice(["acquire", "release", "note"]),
                    rng.randrange(4)) for _ in range(120)]
            self._fuzz(ops)

    def test_same_rid_twice_raises(self):
        sm = SlotManager(2)
        sm.acquire(7, budget=2)
        with pytest.raises(RuntimeError):
            sm.acquire(7, budget=2)

    def test_budget_and_eos_completion(self):
        sm = SlotManager(1, eos_id=42)
        sm.acquire(0, budget=3)
        assert not sm.note_token(0, token=5)
        assert sm.note_token(0, token=42)          # EOS before budget
        st_ = sm.release(0)
        assert st_.taken == 2 and st_.state == ACTIVE
        assert sm.state(0).state == FREE


class TestEngineGuards:
    def test_generate_rejects_bad_inputs(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=32)
        p = jnp.array([1, 2, 3], jnp.int32)
        with pytest.raises(ValueError, match="at least one prompt"):
            eng.generate([], max_new=4)
        with pytest.raises(ValueError, match="max_new"):
            eng.generate([p], max_new=0)
        with pytest.raises(ValueError, match="empty"):
            eng.generate([jnp.zeros((0,), jnp.int32)], max_new=4)

    def test_generate_requests_rejects_bad_requests(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=32)
        p = jnp.array([1, 2], jnp.int32)
        with pytest.raises(ValueError, match="max_new"):
            eng.generate_requests([Request(prompt=p, max_new=0)])
        with pytest.raises(ValueError, match="empty"):
            eng.generate_requests(
                [Request(prompt=jnp.zeros((0,), jnp.int32), max_new=2)])
        assert eng.generate_requests([]) == []

    def test_scheduler_submit_guards(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=16)
        sched = ContinuousScheduler(eng)
        p = jnp.array([1, 2, 3], jnp.int32)
        with pytest.raises(ValueError, match="max_new"):
            sched.submit(Request(prompt=p, max_new=0))
        with pytest.raises(ValueError, match="empty"):
            sched.submit(Request(prompt=jnp.zeros((0,), jnp.int32)))
        # capacity bound (slots.py invariant): the last generated token is
        # never written, so prompt+max_new-1 positions must fit — max_new=14
        # (= 16 positions) is feasible, 15 is the first infeasible budget
        with pytest.raises(ValueError, match="max_len"):
            sched.submit(Request(prompt=p, max_new=15))
        sched.submit(Request(prompt=p, max_new=14))   # exactly max_len: ok
        with pytest.raises(ValueError, match="no bank"):
            sched.submit(Request(prompt=p, max_new=2, adapter_id="t"))


class TestMetricsQuantiles:
    """Satellite: nearest-rank (ceil) quantiles — the old floor index
    `vals[int(0.9*(N-1))]` under-reported the tail at small N."""

    def test_nearest_rank_known_distribution(self):
        from repro.serve.scheduler.metrics import nearest_rank
        vals = list(range(1, 11))                  # 1..10
        assert nearest_rank(vals, 0.50) == 5       # ceil(5) -> 5th
        assert nearest_rank(vals, 0.90) == 9       # ceil(9) -> 9th (old: 8)
        assert nearest_rank(vals, 0.99) == 10      # N < 100 -> the max
        assert nearest_rank([7.0], 0.90) == 7.0
        assert nearest_rank([], 0.90) == 0.0
        # quartile textbook case: 11 samples
        vals = [15, 20, 35, 40, 50] + [60, 70, 80, 90, 100, 110]
        assert nearest_rank(vals, 0.25) == 35      # ceil(2.75) -> 3rd

    def test_summary_percentiles(self):
        from repro.serve.scheduler.metrics import ServingMetrics
        m = ServingMetrics()
        for rid in range(10):
            m.on_arrival(rid, 0.0)
            m.on_token(rid, float(rid + 1))        # TTFTs 1..10
        s = m.summary()
        assert s["ttft_steps_p50"] == 5
        assert s["ttft_steps_p90"] == 9
        assert s["ttft_steps_p99"] == 10


class TestQueueBisect:
    """Satellite: `arrived` cuts at the first arrival > now via bisect —
    behavior must be unchanged vs the full linear scan."""

    def _naive_arrived(self, pending, now):
        return [sr for sr in pending if sr.arrival <= now]

    def test_randomized_trace_no_behavior_change(self):
        from repro.serve.scheduler.queue import RequestQueue
        rng = random.Random(7)
        p = jnp.array([1, 2], jnp.int32)
        for policy in ("fcfs", "resident_first"):
            q = RequestQueue(policy)
            for _ in range(60):
                q.push(Request(prompt=p, max_new=2,
                               adapter_id=rng.choice(
                                   [None, "t-a", "t-b", "t-c"])),
                       arrival=rng.choice([0.0, 1.0, 2.5, 2.5, 7.0, 11.0]))
            now = 0.0
            popped = []
            while len(q):
                assert q.arrived(now) == self._naive_arrived(q.pending, now)
                # admit every other offer: exercises the turned-down path
                flip = [True]
                sr = q.pop_next(now, lambda _: flip.__setitem__(0, not flip[0])
                                or not flip[0], resident=("t-a",))
                if sr is not None:
                    assert sr.arrival <= now
                    popped.append(sr.rid)
                else:
                    now += 0.5
            assert sorted(popped) == list(range(60))

    def test_arrived_is_sorted_prefix(self):
        from repro.serve.scheduler.queue import RequestQueue
        q = RequestQueue()
        p = jnp.array([1], jnp.int32)
        for arr in (5.0, 1.0, 3.0, 1.0, 9.0):
            q.push(Request(prompt=p, max_new=1), arrival=arr)
        assert [sr.arrival for sr in q.arrived(3.0)] == [1.0, 1.0, 3.0]
        assert q.arrived(0.5) == []
        assert len(q.arrived(100.0)) == 5


class TestLockstepCompletionFix:
    def test_budgets_and_chunking(self):
        """generate_requests handles more requests than slots and returns
        exactly max_new tokens each, matching generate() truncation."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=3, max_len=48)
        reqs = _trace([4, 7, 2, 5, 1, 6, 3, 8])
        eng.generate_requests(reqs)
        for at in range(0, len(reqs), 3):
            chunk = reqs[at:at + 3]
            outs = eng.generate([r.prompt for r in chunk],
                                max_new=max(r.max_new for r in chunk))
            for r, o in zip(chunk, outs):
                assert r.out == [int(t) for t in
                                 np.asarray(o[:r.max_new]).reshape(-1)]

    def test_eos_stops_contribution_and_decoding(self):
        """Once every slot hits EOS/budget the chunk's decode loop exits —
        no more max(max_new) over-decoding — and a finished slot records
        nothing past its EOS."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        # EOS must be a token whose FIRST occurrence is at index 2, so
        # generation stops exactly there: probe prompts until one has it
        for prompt in PROMPTS:
            probe = [Request(prompt=jnp.array(prompt, jnp.int32), max_new=10)]
            eng.generate_requests(probe)
            if probe[0].out[2] not in probe[0].out[:2]:
                break
        else:
            pytest.fail("no prompt's greedy stream has a fresh token at 2")
        eos = probe[0].out[2]
        calls = [0]
        real = eng._decode
        eng._decode = lambda *a, **k: (calls.__setitem__(0, calls[0] + 1)
                                       or real(*a, **k))
        reqs = [Request(prompt=jnp.array(prompt, jnp.int32), max_new=10)]
        eng.generate_requests(reqs, eos_id=eos)
        eng._decode = real
        assert reqs[0].out == probe[0].out[:3]     # EOS token included
        assert calls[0] == 2                       # not 9: early exit
