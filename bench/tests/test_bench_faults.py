"""A run with the timed path broken underneath must come out not correct:
the harness's device check is skipped and the rest of a run is driven at a
size the CPU holds, once for each fault the cell can have."""
from __future__ import annotations

import time

import jax
import pytest

from bench.drivers import serve, train
from bench.tests.tiny import tiny_cell

SEED = 2**33 + 17


def _train(wrap_step=None):
    cell = tiny_cell("qwen3-4b.train-sft512")
    return train.run(cell, seed=SEED, seconds=1.0, trace=False,
                     t_start=time.perf_counter(), devices=jax.devices()[:1],
                     wrap_step=wrap_step)


def _unchanged(step_fn, model, tcfg):
    from repro.train import step as train_step
    inner = train_step.make_train_step(model, tcfg)

    @jax.jit
    def step(state, frozen, batch):
        return state, inner(state, frozen, batch)[1]
    return step


def _half_batch(step_fn, model, tcfg):
    def step(state, frozen, batch):
        return step_fn(state, frozen, {k: v[:v.shape[0] // 2]
                                       for k, v in batch.items()})
    return step


def test_sound_training_run_is_correct():
    res = _train()
    assert res.correct, [(c.name, c.value, c.limit) for c in res.checks]
    assert res.attempted > 0 and res.failed == 0


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(fault):
    res = _train(fault)
    assert not res.correct, [(c.name, c.value, c.limit) for c in res.checks]


def _serve(wrap_scheduler=None):
    cell = tiny_cell("qwen3-4b.serve-64tenants")
    return serve.run(cell, seed=SEED, seconds=2.0, trace=False,
                     t_start=time.perf_counter(), devices=jax.devices()[:1],
                     wrap_scheduler=wrap_scheduler)


def _altered_tokens(sched):
    vocab = sched.model.cfg.vocab
    decode = sched._decode

    def altered(*args, **kw):
        nt, cache = decode(*args, **kw)
        return (nt + 1) % vocab, cache
    sched._decode = altered


def test_sound_serving_run_is_correct():
    res = _serve()
    assert res.correct, [(c.name, c.value, c.limit) for c in res.checks]
    assert res.attempted > 0 and res.failed == 0


def test_serving_token_altered_is_not_correct():
    res = _serve(_altered_tokens)
    assert not res.correct, [(c.name, c.value, c.limit) for c in res.checks]


def _reference_in_place(mm):
    """The plain reference, put in the program's place as the step the window
    drives (state in the program's layout), computing with matmul `mm`."""
    from bench.reference import dense_lm

    def wrap(step_fn, model, tcfg):
        cell = tiny_cell("qwen3-4b.train-sft512")
        hp = {k: cell.traffic[k]
              for k in ("learning_rate", "warmup_steps", "grad_clip")}
        ref = dense_lm.adamw_step(dense_lm.Arch(cell.config), hp, mm)

        def coefs(tree):
            return {s: d["c"] for s, d in tree["peft"].items()}

        def tree(c):
            return {"peft": {s: {"c": v} for s, v in c.items()}}

        @jax.jit
        def step(state, frozen, batch):
            t = state["opt"]["count"] + 1
            c, mu, nu, loss, _ = ref(
                coefs(state["trainable"]), coefs(state["opt"]["mu"]),
                coefs(state["opt"]["nu"]), t.astype(jax.numpy.float32),
                frozen["base"],
                {s: d["entries"] for s, d in frozen["peft"].items()},
                batch["tokens"], batch["labels"])
            return dict(state, step=state["step"] + 1, trainable=tree(c),
                        opt={"mu": tree(mu), "nu": tree(nu), "count": t}), \
                {"loss": loss}
        return step
    return wrap


def test_reference_in_the_programs_place_is_correct():
    from bench.reference import dense_lm
    with jax.default_matmul_precision("highest"):
        res = _train(_reference_in_place(dense_lm.matmul_f32))
    assert res.correct, [(c.name, c.value, c.limit) for c in res.checks]


def test_control_in_the_programs_place_is_not_correct():
    from bench.reference import dense_lm
    with jax.default_matmul_precision("highest"):
        res = _train(_reference_in_place(dense_lm.matmul_fp8))
    assert not res.correct, [(c.name, c.value, c.limit) for c in res.checks]
