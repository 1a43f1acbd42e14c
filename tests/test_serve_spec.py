"""Speculative decoding on the continuous runtime (DESIGN.md §Speculation):
greedy draft-then-verify outputs token-identical to the non-speculative
scheduler AND the serial engine — across paged/dense caches, heterogeneous
adapters, EOS traffic, and both drafters — plus drafter/accounting unit
tests and the acceptance-rate counters. The self-drafter must clear the
headline gate: > 1 accepted token per slot per verify step on base-model
traffic (its drafts ARE the target model's argmax)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.checkpoint import adapters as adapter_ckpt
from repro.configs.base import PEFTConfig
from repro.core import adapter as adapter_api
from repro.core import peft as peft_mod
from repro.models import build
from repro.serve import (
    AdapterBank, ContinuousScheduler, Engine, NGramDrafter, Request,
    SelfDrafter,
)
from repro.serve.scheduler.slots import SlotManager


def _cfg(arch="yi-6b"):
    return C.reduced(C.get(arch)).replace(vocab=64, param_dtype="float32",
                                          dtype="float32")


def _base_model():
    model = build(_cfg(), PEFTConfig(method="none"))
    return model, model.init(jax.random.PRNGKey(0))


def _serial(engine, req):
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


# ---------------------------------------------------------------------------
# unit: window accounting + drafters
# ---------------------------------------------------------------------------

class TestNoteWindow:
    def test_budget_clamps_inside_window(self):
        slots = SlotManager(2)
        slots.acquire(0, budget=3)
        assert slots.note_window(0, [5, 6, 7, 8, 9]) == (3, True)

    def test_eos_clamps_inside_window(self):
        slots = SlotManager(2, eos_id=7)
        slots.acquire(0, budget=10)
        assert slots.note_window(0, [5, 7, 6, 6]) == (2, True)
        slots.release(0)

    def test_full_window_not_done(self):
        slots = SlotManager(1, eos_id=7)
        slots.acquire(0, budget=10)
        assert slots.note_window(0, [1, 2, 3]) == (3, False)
        assert slots.state(0).budget == 7
        assert slots.state(0).taken == 3

    def test_window_is_n_sequential_note_tokens(self):
        a, b = SlotManager(1, eos_id=9), SlotManager(1, eos_id=9)
        a.acquire(0, budget=5)
        b.acquire(0, budget=5)
        a.note_window(0, [1, 2, 3])
        for t in [1, 2, 3]:
            b.note_token(0, t)
        assert a.state(0) == b.state(0)

    def test_empty_window_rejected(self):
        slots = SlotManager(1)
        slots.acquire(0, budget=5)
        with pytest.raises(ValueError):
            slots.note_window(0, [])


class TestNGramDrafter:
    def _drafter(self, k=4, ngram=3):
        d = NGramDrafter(k=k, ngram=ngram)
        d.bind(None)
        return d

    def test_lookup_continues_most_recent_match(self):
        d = self._drafter()
        # trailing 3-gram [1,2,3] occurred before, continued by 9,8,7,6
        assert d._lookup([1, 2, 3, 9, 8, 7, 6, 1, 2, 3]) == [9, 8, 7, 6]

    def test_lookup_prefers_recent_occurrence(self):
        d = self._drafter(k=1)
        # [5] occurs twice before the suffix; the later one continues w/ 4
        assert d._lookup([5, 2, 0, 5, 4, 5]) == [4]

    def test_lookup_falls_back_to_shorter_ngram(self):
        d = self._drafter(k=2, ngram=3)
        # no prior [2,3,4]; prior [3,4]? no; prior [4] -> continues with 8
        assert d._lookup([4, 8, 1, 2, 3, 4]) == [8, 1]

    def test_lookup_pads_short_continuation(self):
        d = self._drafter(k=4, ngram=2)
        # prior [1,2] continuation is only [7] before history ends
        assert d._lookup([1, 2, 7, 1, 2]) == [7, 1, 2, 2]

    def test_no_match_repeats_last_token(self):
        d = self._drafter(k=3)
        assert d._lookup([1, 2, 3]) == [3, 3, 3]

    def test_history_lifecycle(self):
        d = self._drafter(k=2)
        d.on_prime(1, np.array([1, 2, 3]), 4)
        d.on_tokens(1, [5, 6])
        assert d._hist[1] == [1, 2, 3, 4, 5, 6]
        d.on_release(1)
        assert 1 not in d._hist

    def test_history_capped(self):
        d = NGramDrafter(k=2, max_history=8)
        d.bind(None)
        d.on_prime(0, np.arange(6), 6)
        d.on_tokens(0, list(range(7, 12)))
        assert len(d._hist[0]) == 8
        assert d._hist[0][-1] == 11

    def test_invalid_params(self):
        with pytest.raises(ValueError):
            NGramDrafter(k=0)
        with pytest.raises(ValueError):
            NGramDrafter(ngram=0)
        with pytest.raises(ValueError):
            SelfDrafter(k=0)


# ---------------------------------------------------------------------------
# exactness: speculative == non-speculative == serial
# ---------------------------------------------------------------------------

class TestSpecExactness:
    @pytest.mark.parametrize("paged", [True, False])
    def test_self_drafter_token_identical(self, paged):
        """Acceptance: greedy speculative output is token-identical to the
        non-speculative scheduler on the staggered trace, paged and dense."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=3, max_len=48)
        arrivals = [0, 0, 1, 2, 3, 5, 8, 9]
        budgets = [4, 7, 2, 5, 1, 6, 3, 8]
        base = _trace(budgets)
        ContinuousScheduler(eng, paged=paged, page_size=8).serve(
            base, arrivals)
        spec = _trace(budgets)
        ContinuousScheduler(eng, paged=paged, page_size=8,
                            drafter=SelfDrafter(k=3)).serve(spec, arrivals)
        assert [r.out for r in spec] == [r.out for r in base]
        for r in spec:
            assert r.out == _serial(eng, r)

    def test_ngram_drafter_token_identical(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=3, max_len=48)
        budgets = [6, 4, 8, 3, 5]
        base = _trace(budgets)
        ContinuousScheduler(eng, page_size=8).serve(base)
        spec = _trace(budgets)
        ContinuousScheduler(eng, page_size=8,
                            drafter=NGramDrafter(k=4)).serve(spec)
        assert [r.out for r in spec] == [r.out for r in base]

    def test_spec_with_eos_token_identical(self):
        """EOS anywhere inside the verify window truncates exactly like the
        per-token loop: learn a token the trace emits, replay with it as
        eos_id on both paths."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        probe = _trace([8, 8])
        ContinuousScheduler(eng, page_size=8).serve(probe)
        eos = probe[0].out[3]
        base = _trace([8, 8, 8, 8])
        ContinuousScheduler(eng, page_size=8, eos_id=eos).serve(base)
        spec = _trace([8, 8, 8, 8])
        ContinuousScheduler(eng, page_size=8, eos_id=eos,
                            drafter=SelfDrafter(k=3)).serve(spec)
        assert [r.out for r in spec] == [r.out for r in base]
        assert any(len(r.out) < 8 for r in spec)   # EOS actually truncated

    def test_heterogeneous_adapters_spec_token_identical(self, tmp_path):
        """Mixed tenants (fourierft + lora + bare base) under the SELF
        drafter: drafts come from the zero bank row, verify gathers each
        slot's tenant row — outputs must still equal each request's serial
        reference exactly."""
        model, params = _base_model()
        profiles = {
            "fourierft": PEFTConfig(method="fourierft", n=16, alpha=25.0,
                                    param_dtype="float32"),
            "lora": PEFTConfig(method="lora", lora_r=2,
                               param_dtype="float32"),
        }
        for i, (tid, m) in enumerate(zip(("tenant-fft", "tenant-lora"),
                                         ("fourierft", "lora"))):
            prof = profiles[m]
            tree = peft_mod.init_adapters(jax.random.PRNGKey(10 + i),
                                          model.sites, prof)
            tree = jax.tree.map(
                lambda x: x + 0.05 if jnp.issubdtype(x.dtype, jnp.floating)
                else x, tree)
            trainable = set(adapter_api.resolve(m).trainable_leaves(prof))
            tree = {s: {k: v for k, v in d.items() if k in trainable}
                    for s, d in tree.items()}
            adapter_ckpt.export_adapter(str(tmp_path), tid, tree, prof)
        bank = AdapterBank(model, profiles, capacity=4,
                           checkpoint_dir=str(tmp_path))
        eng = Engine(model, params, batch_slots=3, max_len=48, bank=bank)
        ids = ["tenant-fft", "tenant-lora", None, "tenant-fft",
               "tenant-lora", None]
        reqs = _trace([5, 3, 6, 2, 4, 3], adapter_ids=ids)
        ContinuousScheduler(eng, page_size=8,
                            drafter=SelfDrafter(k=3)).serve(
            reqs, arrivals=[0, 0, 0, 1, 3, 4])
        for r in reqs:
            assert r.out == _serial(eng, r)


# ---------------------------------------------------------------------------
# throughput gate + metrics
# ---------------------------------------------------------------------------

class TestSpecMetrics:
    def test_self_drafter_accepts_more_than_one_per_step(self):
        """Headline gate: on base-model traffic the self-drafter's drafts
        ARE the target's argmax, so mean emitted tokens per slot-step must
        exceed 1.0 (only budget/EOS clamping can reject)."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=3, max_len=48)
        sched = ContinuousScheduler(eng, page_size=8,
                                    drafter=SelfDrafter(k=3))
        reqs = _trace([8, 8, 8, 8, 8, 8])
        sched.serve(reqs, arrivals=[0, 0, 0, 1, 2, 3])
        s = sched.metrics.summary()
        assert s["spec_tokens_per_step"] > 1.0
        assert s["spec_accept_rate"] > 0.5
        assert s["spec_slot_steps"] > 0
        # histogram totals the emitted tokens the requests actually got;
        # primes emit 1 token each outside the spec path
        emitted = sum(n * c for n, c in sched.metrics.accepted_hist.items())
        assert emitted + len(reqs) == s["total_tokens"]

    def test_per_request_accept_rate_recorded(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        sched = ContinuousScheduler(eng, page_size=8,
                                    drafter=SelfDrafter(k=2))
        reqs = _trace([6, 5])
        sched.serve(reqs)
        for rm in sched.metrics.requests.values():
            assert rm.drafted > 0
            assert rm.accept_rate is not None
            assert 0.0 <= rm.accept_rate <= 1.0
        assert sched.metrics.summary()["spec_drafts_wasted"] >= 0

    def test_no_spec_counters_without_drafter(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        sched = ContinuousScheduler(eng, page_size=8)
        sched.serve(_trace([3, 2]))
        assert "spec_accept_rate" not in sched.metrics.summary()


# ---------------------------------------------------------------------------
# one-step-behind token drain of the plain decode loop
# ---------------------------------------------------------------------------

class TestBufferedEOS:
    def test_eos_traffic_exact_vs_serial_reference(self):
        """The pipelined loop (EOS read from the drained tokens, one step
        late) truncates exactly where the serial generate_requests EOS path
        does."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=3, max_len=48)
        probe = _trace([10])
        ContinuousScheduler(eng, page_size=8).serve(probe)
        eos = probe[0].out[2]
        reqs = _trace([10, 10, 10, 10, 10, 10])
        sched = ContinuousScheduler(eng, page_size=8, eos_id=eos)
        sched.serve(reqs, arrivals=[0, 0, 0, 1, 2, 4])
        for r in reqs:
            ref = [Request(prompt=r.prompt, max_new=r.max_new)]
            eng.generate_requests(ref, eos_id=eos)
            assert r.out == ref[0].out

    def test_eos_streams_one_step_behind_exact_vs_serial(self):
        """Each step's tokens are read one decode step after their own (the
        EOS found there, the one step past it dropped), so no token is held
        longer than a step and every stream still truncates where the
        serial generate_requests EOS path does."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        probe = _trace([10])
        ContinuousScheduler(eng, page_size=8).serve(probe)
        eos = probe[0].out[2]
        reqs = _trace([10, 10, 10, 3])
        sched = ContinuousScheduler(eng, page_size=8, eos_id=eos)
        sched.serve(reqs, arrivals=[0, 0, 3, 3])
        for r in reqs:
            ref = [Request(prompt=r.prompt, max_new=r.max_new)]
            eng.generate_requests(ref, eos_id=eos)
            assert r.out == ref[0].out
        s = sched.metrics.summary()
        assert s["hold_steps_max"] == 1.0
        assert 0.0 < s["hold_steps_mean"] < 1.0

    def test_budget_only_stream_emits_before_any_completion(self):
        """No eos_id and long budgets: decode tokens reach the host one
        step after their own, in ticks before any slot's budget completes
        (the buffered loop held them all until the first completion), and
        the outputs match the serial engine."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        reqs = _trace([12, 16])
        sched = ContinuousScheduler(eng, page_size=8)
        for r in reqs:
            sched.submit(r)
        ticks = []
        while len(sched.queue) or sched.slots.any_active():
            ticks.append(sched.tick())
        first_done = next(i for i, evs in enumerate(ticks)
                          if any(e[0] == "done" for e in evs))
        first_decode_tok = next(i for i, evs in enumerate(ticks)
                                if any(e[0] == "token" and e[3] > 0
                                       for e in evs))
        assert first_decode_tok == 1 < first_done
        for r in reqs:
            assert r.out == _serial(eng, Request(prompt=r.prompt,
                                                 max_new=r.max_new))
        assert sched.metrics.summary()["hold_steps_max"] == 1.0

    @pytest.mark.parametrize("eos", [False, True], ids=["budget", "eos"])
    def test_at_most_one_step_pending_after_each_tick(self, eos):
        """After every plain-loop tick at most the newest step is unread,
        and none when a budget completion fell on it (drained in the same
        tick, so the slot recycles at its completion step)."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        eos_id = None
        if eos:
            probe = _trace([10])
            ContinuousScheduler(eng, page_size=8).serve(probe)
            eos_id = probe[0].out[2]
        reqs = _trace([6, 9, 4, 7, 5])
        sched = ContinuousScheduler(eng, page_size=8, eos_id=eos_id)
        for r, a in zip(reqs, [0, 0, 2, 3, 5]):
            sched.submit(r, arrival=a)
        budget_done = 0
        while len(sched.queue) or sched.slots.any_active():
            evs = sched.tick()
            assert len(sched._pending) <= 1
            for e in evs:
                if e[0] == "done" and e[3] == sched.t and \
                        len(e[2]) == reqs[e[1]].max_new:
                    budget_done += 1
                    assert sched._pending == []
        assert budget_done >= 1
        assert all(r.out is not None for r in reqs)

    def test_budget_only_traffic_unaffected_by_buffering(self):
        """No eos_id: the buffered loop drains exactly at budget
        completions, so admission/completion step stamps match the
        historical per-step loop's timing."""
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        reqs = _trace([4, 6, 3, 5])
        sched = ContinuousScheduler(eng, page_size=8)
        sched.serve(reqs, arrivals=[0, 0, 2, 3])
        done = {r.out is not None for r in reqs}
        assert done == {True}
        m = sched.metrics
        for rm in m.requests.values():
            assert rm.finished is not None
            # every token carries a step stamp inside the run
            assert rm.first_token is not None
        assert m.total_tokens == sum(len(r.out) for r in reqs)
