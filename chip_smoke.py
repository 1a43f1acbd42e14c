#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, through the entry points a user
calls. It proves the system starts and computes the right thing on the chip;
its times are smoke numbers, not benchmark numbers.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips (a 2x2 v5e host)

One chip, qwen3-4b at its published widths (36 layers, d_model 2560, GQA
32/8, d_ff 9728, vocab 151936; random weights from a seed), FourierFT with
the paper defaults (n=1000 on wq/wv, merged, kernel_backend=auto):

  1. policy  — `model.explain_kernels()`; every targeted deltaw site and the
               paged_attention op must resolve to the compiled Pallas kernel.
  2. kernels — the stacked fourier_deltaw forward and its coefficient
               gradient at the model's site widths, and paged_attention (the
               decode step W=1 and a speculative verify window W=5), against
               their einsum references at float32 precision.
  3. train   — `init_state` -> `shard_train_state` -> `make_sharded_train_step`
               -> `loop.run` on SyntheticLM; finite losses, no anomalies;
               the trained adapter is exported with checkpoint/adapters.py.
  4. serve   — `launch.api.build_scheduler` with the exported adapter in the
               bank, behind the HTTP `GatewayServer`; base and
               `adapter:<id>` requests on the paged cache must all succeed,
               and one request's greedy tokens must match `Engine.generate`.

Four chips, yi-9b (`--chips 4` runs only this): the same program on a 1x4
(data x model) mesh and on a one-device mesh, at published widths with the
depth cut to 8 layers so one chip holds it — a train step and a greedy decode
must agree; then yi-9b at full depth (about 17.6 GB of bf16 weights, more
than one chip holds) takes a few train steps and decodes a few tokens, with
per-device memory printed to show the weights are spread.

The last line of standard output is `{"ok": true, "device": {...}}`. Any
failure — including JAX finding no TPU — exits non-zero without it.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

# float32 tolerances: max |got - ref| over max |ref|. A bf16 matmul pass
# anywhere in the kernel would land near 4e-3.
KERNEL_TOL = 1e-4
# every greedy token must be the reference forward's top choice after the
# same prefix, or within this much of it (in units of the standard deviation
# of that row of reference logits): two numerically different paths may part
# at a near-tie, while a wrong token sits far below the top.
NEAR_TIE = 0.05
# backend the kernel policy must pick on the chip
EXPECT_BACKEND = "pallas"

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 256, 4
SERVE_MAX_NEW = 8
ADAPTER_ID = "smoke-ft"


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class phase:
    """Labelled wall-clock span (host clock, compilation included)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"--- {self.name}")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"--- {self.name} ok: {time.perf_counter() - self.t0:.1f} s "
                "wall (smoke, not a benchmark)")


def rel_err(got, ref) -> float:
    """max |got - ref| / max |ref|, reduced on the device (a ΔW stack is
    1.5 GB: copying it to the host would dominate the phase)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                      1e-30)
    return float(err(got, ref))


def memory_report(devices, label: str):
    rows = []
    for d in devices:
        st = d.memory_stats() or {}
        rows.append((d.id, st.get("bytes_in_use", 0),
                     st.get("peak_bytes_in_use", 0)))
        log(f"{label}: device {d.id} bytes_in_use={rows[-1][1]} "
            f"peak_bytes_in_use={rows[-1][2]}")
    return rows


# ---------------------------------------------------------------------------
# shared checks
# ---------------------------------------------------------------------------

def check_policy(model, ops=("deltaw", "paged_attention")) -> None:
    text = model.explain_kernels()
    print(text, flush=True)
    res = model.kernel_policy.resolutions
    wanted = [r for r in res if r.op in ops]
    check({r.op for r in wanted} == set(ops),
          f"policy lacks an entry for one of {ops}:\n{text}")
    bad = [f"{r.op}@{r.site}->{r.backend}" for r in wanted
           if r.backend != EXPECT_BACKEND]
    check(not bad, f"resolved off {EXPECT_BACKEND}: {bad}")


def check_greedy(label: str, got, want, prompt, logits_along) -> None:
    """`got` must equal `want` up to where they part, and every token of
    `got` — and `want`'s token where they part — must be the reference's
    greedy choice or a near-tie of it. `logits_along(tokens)` gives the
    reference's next-token logits at every position of `tokens`, so one
    teacher-forced forward checks the whole stream."""
    got, want = [int(t) for t in got], [int(t) for t in want]
    check(len(got) == len(want), f"{label}: {len(got)} vs {len(want)} tokens")
    lg = np.asarray(logits_along(list(prompt) + got[:-1]), np.float64)
    rows = lg[len(prompt) - 1:]
    parted, worst = None, 0.0
    for i, row in enumerate(rows):
        top, bound = float(row.max()), NEAR_TIE * float(row.std())
        picks = [got[i]] + ([want[i]] if parted is None and
                            want[i] != got[i] else [])
        for t in picks:
            gap = top - float(row[t])
            worst = max(worst, gap / bound)
            check(gap <= bound, f"{label}: token {i} is {t}, the reference "
                  f"prefers {int(row.argmax())} by {gap:.4g} > near-tie "
                  f"bound {bound:.4g}")
        if parted is None and want[i] != got[i]:
            parted = i
    how = ("identical" if parted is None else
           f"equal for {parted}, then parted at a near-tie")
    log(f"{label}: {len(got)} greedy tokens {how}; largest gap to the "
        f"reference's top choice {worst:.3g} x the near-tie bound")


def forward_logits(model, params, extra, batch_rows: int):
    """Reference next-token logits at every position of a token list (row 0
    of a batch of `batch_rows` copies, as the engine's bank wiring
    expects)."""
    import jax
    import jax.numpy as jnp
    fwd = jax.jit(model.forward)

    def logits_along(tokens):
        toks = jnp.tile(jnp.asarray(tokens, jnp.int32)[None], (batch_rows, 1))
        logits, _ = fwd(params, {"tokens": toks, **extra})
        return np.asarray(logits[0].astype(jnp.float32))
    return logits_along


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernel_phase(model) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import api as kernel_api
    from repro.kernels import paged_attention as pa

    method, peft = model.method, model.peft
    ref_peft = peft.replace(kernel_backend="einsum")
    for site in model.sites:
        if site.name.split("/")[-1] not in peft.target_modules:
            continue
        t0 = time.perf_counter()
        ad = method.init_site(jax.random.PRNGKey(1), site, peft)
        g = jax.random.normal(jax.random.PRNGKey(2),
                              (site.stack, site.d_in, site.d_out))

        def delta(a, p):
            return method.site_delta(a, site, p)

        def dc(a, g, p):
            loss = lambda c: jnp.vdot(g, delta({**a, "c": c}, p))
            return jax.grad(loss)(a["c"])

        fwd = jax.jit(lambda a: delta(a, peft)).lower(ad).compile()
        if EXPECT_BACKEND == "pallas":
            check("tpu_custom_call" in fwd.as_text(),
                  f"{site.name}: no Pallas kernel in the compiled deltaw")
        got_w = fwd(ad)
        got_c = jax.jit(lambda a, g: dc(a, g, peft))(ad, g)
        with jax.default_matmul_precision("highest"):
            ref_w = jax.jit(lambda a: delta(a, ref_peft))(ad)
            ref_c = jax.jit(lambda a, g: dc(a, g, ref_peft))(ad, g)
        e_w, e_c = rel_err(got_w, ref_w), rel_err(got_c, ref_c)
        log(f"{site.name} {site.stack}x{site.d_in}x{site.d_out} n={peft.n}: "
            f"deltaw rel err {e_w:.3g}, dc rel err {e_c:.3g} "
            f"(tolerance {KERNEL_TOL}); {time.perf_counter() - t0:.1f} s")
        check(e_w <= KERNEL_TOL and e_c <= KERNEL_TOL,
              f"{site.name}: kernel disagrees with its einsum reference")
        del got_w, ref_w, g

    cfg = model.cfg
    op = kernel_api.resolve_op("paged_attention", pa.OWNER, peft)
    check(op.backend == EXPECT_BACKEND, f"paged_attention -> {op.backend}")
    B, ps, pps, n_pages = 4, 16, 8, 40
    rng = np.random.default_rng(0)
    bt = jnp.asarray(rng.permutation(n_pages)[:B * pps].reshape(B, pps),
                     jnp.int32)
    kv_len = jnp.asarray([1, 37, 100, pps * ps - 5], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    kp = jax.random.normal(ks[0], (n_pages, ps, cfg.n_kv, cfg.head_dim))
    vp = jax.random.normal(ks[1], (n_pages, ps, cfg.n_kv, cfg.head_dim))
    for W in (1, 5):
        t0 = time.perf_counter()
        q = jax.random.normal(ks[2], (B, W, cfg.n_heads, cfg.head_dim))
        got = jax.jit(op.fn)(q, kp, vp, bt, kv_len)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(pa.paged_attention_einsum)(q, kp, vp, bt, kv_len)
        e = rel_err(got, ref)
        log(f"paged_attention W={W} H={cfg.n_heads} K={cfg.n_kv} "
            f"dh={cfg.head_dim} page={ps}: rel err {e:.3g} "
            f"(tolerance {KERNEL_TOL}); {time.perf_counter() - t0:.1f} s")
        check(e <= KERNEL_TOL, f"paged_attention W={W} disagrees")


def train_phase(model, bank_dir: str, seed: int = 0) -> None:
    import jax
    from repro.checkpoint import adapters as adapter_ckpt
    from repro.configs.base import TrainConfig
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.train import loop, step as train_step

    cfg = model.cfg
    tcfg = TrainConfig(learning_rate=1e-2, total_steps=TRAIN_STEPS,
                       warmup_steps=1, seed=seed)
    mesh = make_host_mesh(model=1, devices=jax.devices()[:1])
    data = SyntheticLM(vocab=cfg.vocab, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                       seed=seed, task_seed=7)
    t0 = time.perf_counter()
    state, frozen = train_step.init_state(model, tcfg,
                                          jax.random.PRNGKey(seed), mesh=mesh)
    state, frozen, st_sh, fr_sh = train_step.shard_train_state(
        model, state, frozen, mesh)
    jax.block_until_ready((state, frozen))
    log(f"init + placement {time.perf_counter() - t0:.1f} s")
    step_fn, batch_sh = train_step.make_sharded_train_step(
        model, tcfg, mesh, state, frozen, data.batch_at(0),
        shardings=(st_sh, fr_sh))
    t0 = time.perf_counter()
    with mesh:
        compiled = step_fn.lower(state, frozen,
                                 jax.device_put(data.batch_at(0), batch_sh)
                                 ).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    log(f"train step batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, remat "
        f"{model.remat}: compile {time.perf_counter() - t0:.1f} s, "
        f"memory_analysis args {mem.argument_size_in_bytes} + temp "
        f"{mem.temp_size_in_bytes} B = {total} B per device")
    del compiled
    state, report = loop.run(
        step_fn, state, frozen, data, tcfg, log_every=1, mesh=mesh,
        batch_sharding=batch_sh, state_sharding=st_sh,
        log_fn=lambda m: log(f"train {m} (smoke)"))
    losses = report.losses
    log(f"train losses {losses}, anomalies {report.anomalies}")
    check(report.steps_run == TRAIN_STEPS, f"ran {report.steps_run} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(report.anomalies == 0, f"{report.anomalies} anomalous steps")
    adapter_ckpt.export_adapter(bank_dir, ADAPTER_ID,
                                state["trainable"]["peft"], model.peft)
    log(f"exported trained adapter {ADAPTER_ID!r}")


async def _gateway_traffic(sched, vocab: int):
    from benchmarks import loadgen
    from repro.serve.gateway import GatewayServer

    server = GatewayServer(sched, max_queue=32,
                           default_max_new=SERVE_MAX_NEW)
    await server.start()
    rng = np.random.default_rng(11)
    payloads = []
    for i, model_name in enumerate(("base", f"adapter:{ADAPTER_ID}",
                                    "base", f"adapter:{ADAPTER_ID}")):
        prompt = [int(t) for t in rng.integers(0, vocab, 12 + 3 * i)]
        payloads.append({"model": model_name, "prompt": prompt,
                         "max_tokens": SERVE_MAX_NEW, "stream": i >= 2})
    try:
        t0 = time.perf_counter()
        results = await asyncio.gather(*(
            loadgen.send_request(server.host, server.port, p, retries=4,
                                 timeout_s=900.0) for p in payloads))
        wall = time.perf_counter() - t0
    finally:
        await server.close()
    return results, wall, dict(server.responses)


def serve_phase(cfg, bank_dir: str, seed: int = 0) -> None:
    import jax.numpy as jnp
    from repro.launch.api import add_model_args, build_scheduler

    ap = argparse.ArgumentParser()
    add_model_args(ap)
    sargs = ap.parse_args(["--arch", cfg.name, "--bank-dir", bank_dir,
                           "--seed", str(seed), "--slots", "4",
                           "--max-len", "128"])
    t0 = time.perf_counter()
    sched, tenants = build_scheduler(sargs)
    log(f"scheduler up in {time.perf_counter() - t0:.1f} s: tenants "
        f"{tenants}, {sched.n_slots} slots, paged cache")
    check(ADAPTER_ID in tenants, f"adapter not in the bank: {tenants}")
    # the served base has no deltaw sites: tenants ride the adapter bank
    check_policy(sched.model, ops=("paged_attention",))
    results, wall, statuses = asyncio.run(_gateway_traffic(sched,
                                                           cfg.vocab))
    n_tok = 0
    for r in results:
        name = r.payload["model"]
        stream = r.payload["stream"]
        log(f"{name} stream={stream}: status {r.status} finish {r.finish} "
            f"tokens {r.tokens}")
        check(r.ok and r.status == 200, f"{name}: failed request "
              f"(status {r.status}, {getattr(r, 'error', None)})")
        check(len(r.tokens) == SERVE_MAX_NEW,
              f"{name}: {len(r.tokens)} tokens")
        n_tok += len(r.tokens)
    check(set(statuses) == {200}, f"non-200 responses: {statuses}")
    log(f"gateway served {len(results)} requests, {n_tok} tokens in "
        f"{wall:.1f} s wall, compilation included (smoke)")
    eng = sched.engine
    for r in results:
        if r.payload["model"] != f"adapter:{ADAPTER_ID}" or \
                r.payload["stream"]:
            continue
        prompt = r.payload["prompt"]
        want = eng.generate([jnp.asarray(prompt, jnp.int32)],
                            max_new=SERVE_MAX_NEW,
                            adapter_ids=[ADAPTER_ID])[0]
        params, extra = eng._batch_extra([ADAPTER_ID])
        check_greedy("gateway vs Engine.generate", r.tokens,
                     np.asarray(want).reshape(-1), prompt,
                     forward_logits(eng.model, params, extra, eng.batch))


def one_chip() -> None:
    import repro.configs as configs
    from repro.configs.base import PEFTConfig
    from repro.models import build

    cfg = configs.get("qwen3-4b")
    model = build(cfg, PEFTConfig(), remat="full")
    log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}; method {model.peft.method} n={model.peft.n} "
        f"targets {model.peft.target_modules} {model.peft.strategy}")
    with phase("policy"):
        check_policy(model)
    with phase("kernels"):
        kernel_phase(model)
    gc.collect()
    with tempfile.TemporaryDirectory() as bank_dir:
        with phase("train"):
            train_phase(model, bank_dir)
        gc.collect()
        with phase("serve"):
            serve_phase(cfg, bank_dir)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _train_on(model, mesh, steps: int, seed: int = 0):
    import jax
    from repro.configs.base import TrainConfig
    from repro.data import SyntheticLM
    from repro.train import loop, step as train_step

    tcfg = TrainConfig(learning_rate=1e-2, total_steps=steps, warmup_steps=1,
                       seed=seed)
    data = SyntheticLM(vocab=model.cfg.vocab, batch=TRAIN_BATCH,
                       seq=TRAIN_SEQ, seed=seed, task_seed=7)
    state, frozen = train_step.init_state(model, tcfg,
                                          jax.random.PRNGKey(seed), mesh=mesh)
    state, frozen, st_sh, fr_sh = train_step.shard_train_state(
        model, state, frozen, mesh)
    step_fn, batch_sh = train_step.make_sharded_train_step(
        model, tcfg, mesh, state, frozen, data.batch_at(0),
        shardings=(st_sh, fr_sh))
    shape = "x".join(map(str, mesh.devices.shape))
    state, report = loop.run(
        step_fn, state, frozen, data, tcfg, log_every=1, mesh=mesh,
        batch_sharding=batch_sh, state_sharding=st_sh,
        log_fn=lambda m: log(f"mesh {shape} train {m} (smoke)"))
    check(report.steps_run == steps and report.anomalies == 0
          and all(math.isfinite(x) for x in report.losses),
          f"mesh {shape}: losses {report.losses}, anomalies "
          f"{report.anomalies}")
    return state, frozen, report


def _decode_on(model, mesh, prompts, max_new: int, seed: int = 0):
    import jax
    from repro.dist import sharding as shd
    from repro.serve import Engine

    params, _ = shd.init_placed(
        model.init, jax.random.PRNGKey(seed), mesh,
        lambda t: shd.state_specs(t, mesh, model.cfg))
    eng = Engine(model, params, batch_slots=len(prompts), max_len=64,
                 mesh=mesh)
    out = eng.generate(prompts, max_new=max_new)
    return eng, [np.asarray(o).reshape(-1) for o in out]


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import repro.configs as configs
    from repro.configs.base import PEFTConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.serve import Engine
    from repro.train.step import join_params

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh4 = make_host_mesh(model=4, devices=devs[:4])
    mesh1 = make_host_mesh(model=1, devices=devs[:1])
    full = configs.get("yi-9b")
    cut = full.replace(num_layers=8)
    rng = np.random.default_rng(5)
    prompts = [jnp.asarray(rng.integers(0, full.vocab, 12), jnp.int32)
               for _ in range(2)]

    with phase(f"yi-9b cut to {cut.num_layers} layers: 1x4 mesh vs one "
               "device"):
        model = build(cut, PEFTConfig(), remat="full")
        check_policy(model)
        reports = {}
        for name, mesh in (("1x1", mesh1), ("1x4", mesh4)):
            state, frozen, reports[name] = _train_on(model, mesh, steps=2)
            del state, frozen
            gc.collect()
        l1, l4 = reports["1x1"].losses, reports["1x4"].losses
        err = max(abs(a - b) / abs(b) for a, b in zip(l4, l1))
        log(f"train losses 1x4 {l4} vs one device {l1}: max rel diff "
            f"{err:.3g} (tolerance 1e-2)")
        check(err <= 1e-2, "sharded train step disagrees with one device")
        outs = {}
        for name, mesh in (("1x1", mesh1), ("1x4", mesh4)):
            eng, outs[name] = _decode_on(model, mesh, prompts, max_new=8)
            if name == "1x1":
                ref_eng = eng
            else:
                del eng
        params, extra = ref_eng._batch_extra(None)
        logits_along = forward_logits(ref_eng.model, params, extra,
                                      ref_eng.batch)
        for i, p in enumerate(prompts):
            check_greedy(f"prompt {i} greedy decode 1x4 vs one device",
                         outs["1x4"][i], outs["1x1"][i], np.asarray(p),
                         logits_along)
        del ref_eng, params, logits_along
        gc.collect()

    with phase(f"yi-9b full depth ({full.num_layers} layers) on the 1x4 "
               "mesh"):
        model = build(full, PEFTConfig(), remat="full")
        state, frozen, report = _train_on(model, mesh4, steps=3)
        rows = memory_report(devs[:4], "after train")
        used = [r[1] for r in rows]
        check(max(used) <= 1.25 * min(used),
              f"weights not spread evenly over the devices: {used}")
        log(f"full-depth train losses {report.losses}")
        params = join_params(model, state["trainable"], frozen)
        del state, frozen
        gc.collect()
        eng = Engine(model, params, batch_slots=len(prompts), max_len=64,
                     mesh=mesh4)
        del params
        t0 = time.perf_counter()
        out = eng.generate(prompts, max_new=4)
        toks = [np.asarray(o).reshape(-1).tolist() for o in out]
        log(f"full-depth greedy decode {toks} in "
            f"{time.perf_counter() - t0:.1f} s (smoke)")
        check(all(len(t) == 4 and all(0 <= x < full.vocab for x in t)
                  for t in toks), f"bad decode output {toks}")
        memory_report(devs[:4], "after decode")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: only the four-chip "
                         "yi-9b phase")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repo ({e})",
              file=sys.stderr)
        return 2
    cache_dir = setup_compile_cache()

    import jax

    counts = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this smoke "
              "never falls back to the CPU", file=sys.stderr)
        return 1
    log(f"device {dev.device_kind} x {len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"all phases ok in {time.perf_counter() - t0:.1f} s wall; "
        f"persistent compile cache hits {counts['hits']}, misses "
        f"{counts['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
