"""Training cells: the step from `repro.train.step.make_sharded_train_step`
with its state, fed a fresh batch of host-drawn tokens every step.

Set-up builds one object — the compiled step with its state — and drives it
through the first `checked_steps` steps with `drive`, the window's own call
and feed (step 1 compiles). The window then continues the same `drive` for
`--seconds`: it keeps several seconds of steps dispatched ahead of the one
whose loss it reads, so that a stall of the host leaves the chip busy, and
when the time is up it dispatches nothing more, waits for every step it
sent, and reads the clock after that wait. Afterwards the program's state is
freed and the plain reference (bench/reference/dense_lm.py) runs the same
first steps from the same weights and batches; the program's losses, first
gradient (read back from AdamW's first moment) and coefficient change are
compared with it.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Callable, Dict, Optional

import numpy as np

from bench import harness
from bench.harness import Check, Cell, RunResult

# seconds of steps kept in flight ahead of the one whose loss is read: a
# host stall shorter than that leaves the chip busy
AHEAD_S = 6.0
MAX_AHEAD = 32


class TokenFeed:
    """Step-keyed batches drawn on the host from the
    seed — uniform tokens over the vocabulary, labels the next token."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed

    def batch_at(self, step: int, shard: int = 0,
                 num_shards: int = 1) -> Dict[str, np.ndarray]:
        import jax
        with jax.profiler.TraceAnnotation("bench.feed"):
            rng = harness.np_rng(self.seed, 7, step)
            rows = rng.integers(0, self.vocab, (self.batch, self.seq + 1),
                                dtype=np.int32)
            return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


def _host(tree):
    import jax
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def leaf_norm_gap(got: Dict, ref: Dict) -> float:
    """Worst leaf of |‖got‖ − ‖ref‖| over max(‖ref leaf‖, median ‖ref
    leaf‖). Leaves whose reference norm is under a thousandth of the median
    leaf's are nought to rounding and left out."""
    ref_n = {k: _norm(v) for k, v in ref.items()}
    med = float(np.median(list(ref_n.values())))
    gaps = [abs(_norm(got[k]) - ref_n[k]) / max(ref_n[k], med)
            for k in ref if ref_n[k] >= 1e-3 * med]
    return max(gaps) if gaps else float("nan")


def _site_coefs(trainable: Dict) -> Dict:
    return {site: d["c"] for site, d in trainable["peft"].items()}


def drive(step_fn, state, frozen, feed, b_sh, mesh, start: int, *,
          steps: Optional[int] = None, until: Optional[float] = None,
          ahead: int = 1):
    """Run steps `start`, `start + 1`, ... of `step_fn`, each on the feed's
    batch of that step, with `ahead` steps dispatched past the one whose
    loss is read. Dispatch stops after `steps` steps or once the clock
    (`time.perf_counter`) passes `until`; every step sent is waited for.
    -> (state, losses, the clock at each loss read)"""
    import jax
    losses, stamps, pending = [], [], collections.deque()

    def read():
        with jax.profiler.TraceAnnotation("bench.loss_read"):
            losses.append(float(jax.device_get(pending.popleft())))
        stamps.append(time.perf_counter())

    k = start
    with mesh:
        while ((steps is None or k < start + steps)
               and (until is None or time.perf_counter() < until)):
            batch = jax.device_put(feed.batch_at(k), b_sh)
            state, metrics = step_fn(state, frozen, batch)
            pending.append(metrics["loss"])
            k += 1
            while len(pending) > ahead:
                read()
        while pending:
            read()
    return state, losses, stamps


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, devices, wrap_step: Optional[Callable] = None
        ) -> RunResult:
    """`wrap_step(step_fn, model, tcfg) -> step_fn` plants a fault in the
    timed path (tests only)."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.train import step as train_step

    from bench import weights
    from bench.reference import dense_lm

    conf, tr, tc = cell.config, cell.traffic, cell.config["train"]
    cfg = harness.model_config(conf)
    model = build(cfg, harness.peft_config(conf), remat=tc["remat"])
    n_model = tc["mesh"]["model"]
    n_dev = tc["mesh"]["data"] * n_model
    mesh = make_host_mesh(model=n_model, devices=devices[:n_dev])
    checked = int(tr["checked_steps"])
    tcfg = TrainConfig(learning_rate=tr["learning_rate"],
                       warmup_steps=tr["warmup_steps"],
                       schedule=tr["schedule"], grad_clip=tr["grad_clip"],
                       total_steps=checked, remat=tc["remat"])
    B, S = int(tc["batch"]), int(tr["seq_len"])
    feed = TokenFeed(cfg.vocab, B, S, seed)

    harness.log(f"{cell.name}: {cfg.num_layers} layers, batch {B} x {S}, "
                f"mesh {tc['mesh']}")
    state, frozen, st_sh, fr_sh, entries = weights.train_state(
        model, tcfg, mesh, seed)
    coefs0 = _host(_site_coefs(state["trainable"]))
    step_fn, b_sh = train_step.make_sharded_train_step(
        model, tcfg, mesh, state, frozen, feed.batch_at(0),
        shardings=(st_sh, fr_sh))
    if wrap_step is not None:
        step_fn = wrap_step(step_fn, model, tcfg)

    # the first steps, through the window's own call and feed; the last
    # one, warm, times a step
    t0 = time.perf_counter()
    state, losses, _ = drive(step_fn, state, frozen, feed, b_sh, mesh, 0,
                             steps=1)
    step_s = time.perf_counter() - t0
    # AdamW's first moment after one step is (1 - b1) * gradient
    g1 = {s: v / (1.0 - tcfg.b1) for s, v in
          _host(_site_coefs(state["opt"]["mu"])).items()}
    for k in range(1, checked):
        t0 = time.perf_counter()
        state, more, _ = drive(step_fn, state, frozen, feed, b_sh, mesh, k,
                               steps=1)
        losses += more
        step_s = time.perf_counter() - t0
    ahead = max(1, min(MAX_AHEAD, round(AHEAD_S / step_s)))
    coefs_k = _host(_site_coefs(state["trainable"]))
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up {setup_s:.1f} s; first losses {losses}; a step "
                f"{step_s:.3f} s, {ahead} kept ahead in the window")

    with harness.profiled(cell, trace) as tdir:
        t0 = time.perf_counter()
        state, w_losses, stamps = drive(step_fn, state, frozen, feed, b_sh,
                                        mesh, checked, until=t0 + seconds,
                                        ahead=ahead)
        window_s = time.perf_counter() - t0
    steps = len(w_losses)
    bad = sum(1 for x in w_losses if not np.isfinite(x))
    tokens_per_s = steps * B * S / window_s
    gaps = np.diff([t0] + stamps)
    harness.log(f"window {window_s:.2f} s: {steps} steps, "
                f"{tokens_per_s:.1f} tokens/s; between loss reads median "
                f"{np.median(gaps):.3f} s, longest {gaps.max():.3f} s")
    mem = harness.peak_memory_bytes(devices[:n_dev])
    del state, step_fn
    gc.collect()

    # the plain reference, once the program's state is freed
    t0 = time.perf_counter()
    arch = dense_lm.Arch(conf)
    batches = [(jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
               for b in (feed.batch_at(i) for i in range(checked))]
    hp = {k: tr[k] for k in ("learning_rate", "warmup_steps", "grad_clip")}
    with jax.default_matmul_precision("highest"):
        r_losses, r_g1, r_coefs = dense_lm.train(
            arch, hp, {s: jnp.asarray(v) for s, v in coefs0.items()},
            frozen["base"], {s: jnp.asarray(v) for s, v in entries.items()},
            batches)
    r_g1, r_coefs = _host(r_g1), _host(r_coefs)
    harness.log(f"reference {time.perf_counter() - t0:.1f} s; losses "
                f"{r_losses}")
    lim = cell.limits
    checks = [
        Check("loss", max(abs(a - b) / abs(b)
                          for a, b in zip(losses, r_losses)), lim["loss"]),
        Check("grad1", leaf_norm_gap(g1, r_g1), lim["grad1"]),
        Check("change", leaf_norm_gap(
            {s: coefs_k[s] - coefs0[s] for s in coefs0},
            {s: r_coefs[s] - coefs0[s] for s in coefs0}), lim["change"]),
    ]
    ctx = {"kind": "train", "config": conf, "traffic": tr, "batch": B,
           "seq": S, "steps": steps, "window_s": window_s,
           "tokens_per_s": tokens_per_s, "traced_steps": steps,
           "mesh": tc["mesh"],
           "sites": [(s.name, s.d_in, s.d_out, s.stack) for s in model.sites
                     if s.name in entries],
           "n": model.peft.n}
    return RunResult(
        attempted=steps, failed=bad,
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem, context=ctx, trace_dir=tdir,
        notes=[f"kernel policy:\n{model.explain_kernels()}"])
