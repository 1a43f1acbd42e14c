"""Serving cells: the OpenAI-compatible gateway (`repro.serve.gateway
.GatewayServer`) over the continuous scheduler, paged KV cache and FourierFT
adapter bank, driven over HTTP by bench/loadgen.py in a process of its own.

Set-up draws the base weights and every tenant's coefficients from the seed
on the device, builds the engine, bank and scheduler the way
`repro.launch.api.build_scheduler` does (with the benchmark's weights in
place of the program's initializer), starts the gateway and warms every
prompt bucket the mix can hit with a few streamed requests. The load
generator then sends the mix's open loop: a lead-in, so the window opens in
steady state, then `--seconds` of requests. Requests due in the window are
the ones measured; each is waited for after the window closes.

Afterwards the server and its cache are freed and the plain reference runs
over a sample of the window's finished requests (the longest among them):
at every served position, the gap by which the served token's reference
logit lies below the reference's best, in standard deviations of that
position's reference logits.
"""
from __future__ import annotations

import asyncio
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import harness, loadgen, traffic_gen
from bench.harness import Check, Cell, RunResult

LOADGEN = Path(loadgen.__file__).resolve()
# a request not answered this long after the window closes never comes
DRAIN_S = 60.0


def nearest_rank(vals: Sequence[float], q: float) -> float:
    """The ceil(q·N)-th smallest value (1-indexed); nan when empty."""
    v = sorted(vals)
    if not v:
        return float("nan")
    return v[max(0, min(len(v) - 1, math.ceil(q * len(v)) - 1))]


def tenant_id(i: int) -> str:
    return f"t{i:02d}"


def _ok(r: Dict, max_tokens: int, eos: Optional[int]) -> bool:
    """Finished as asked: `max_tokens` tokens, or a stop on the EOS id."""
    if r["status"] != 200 or r["error"] or not r["tokens"]:
        return False
    if r["finish"] == "length":
        return len(r["tokens"]) == max_tokens
    return r["finish"] == "stop" and r["tokens"][-1] == eos


def _failed(r: Dict, max_tokens: int, eos: Optional[int]) -> bool:
    """Refused, broken, or without a first token by the deadline. A stream
    still running when the load generator's deadline cut it is not a
    failure: its tokens so far were served."""
    if r["status"] != 200 or not r["stamps"]:
        return True
    if r["error"] == loadgen.CUT:
        return False
    return not _ok(r, max_tokens, eos)


def window_metrics(results: List[Dict], planned, w0: float, w1: float,
                   eos: Optional[int], miss_at: float) -> Dict:
    """End-to-end numbers of the requests due in [w0, w1). A request that
    failed counts with a time to first token of `miss_at` - due, beyond
    every limit."""
    ttft, itl, failed, win = [], [], 0, []
    for r, p in zip(results, planned):
        if not (w0 <= r["due"] < w1):
            continue
        win.append(r)
        bad = _failed(r, p.max_tokens, eos)
        failed += bad
        ttft.append((miss_at if bad else r["stamps"][0]) - r["due"])
        if not bad:
            s = r["stamps"]
            itl += [b - a for a, b in zip(s, s[1:])]
    toks = sum(1 for r in results for t in r["stamps"] if w0 <= t < w1)
    lag = [r["sent"] - r["due"] for r in win if r["sent"] is not None]
    return {"attempted": len(win), "failed": failed,
            "ttft_p50_ms": 1e3 * nearest_rank(ttft, 0.50),
            "ttft_p90_ms": 1e3 * nearest_rank(ttft, 0.90),
            "itl_p99_ms": 1e3 * nearest_rank(itl, 0.99),
            "serve_tokens_per_s": toks / (w1 - w0),
            "loadgen_lag_p99_ms": 1e3 * nearest_rank(lag, 0.99),
            "n_itl": len(itl)}


async def _warm(server, cell: Cell, planned, eos: Optional[int]):
    """Stream one short request for every prompt length the run's traffic
    holds (the scheduler compiles one prime per power-of-two bucket, and
    host-side array work compiles per length), all at once so the decode
    step also runs with several slots busy."""
    rng = np.random.default_rng(0)
    jobs = []
    for i, n in enumerate(sorted({len(p.prompt) for p in planned})):
        toks = rng.integers(1, server.vocab, n)
        toks[toks == eos] = 1
        payload = {"model": f"adapter:{tenant_id(i % cell.traffic['tenants'])}",
                   "prompt": [int(t) for t in toks],
                   "max_tokens": cell.traffic["warmup_tokens"],
                   "stream": True}
        jobs.append(loadgen.fire(server.host, server.port, payload,
                                 loadgen.new_result(time.monotonic(), 0.0),
                                 600.0))
    res = await asyncio.gather(*jobs)
    bad = [r for r in res if r["status"] != 200 or r["error"]]
    if bad:
        raise harness.BenchError(f"warm-up request failed: {bad[0]}")


async def session(cell: Cell, server, planned, seconds: float,
                  trace: bool, workdir: Path):
    """Run the load generator over `planned` and mark the window.
    -> (results, w0, w1, scheduler numbers of the window, trace dir)"""
    sched = server.sched
    mix = cell.traffic
    t0 = time.monotonic() + 1.0
    w0 = t0 + mix["lead_in_s"]
    w1 = w0 + seconds
    sched_path, out_path = workdir / "schedule.json", workdir / "results.json"
    sched_path.write_text(json.dumps({
        "host": server.host, "port": server.port, "t0": t0,
        "deadline": w1 + DRAIN_S,
        "timeout_s": seconds + mix["lead_in_s"] + DRAIN_S,
        "requests": [{"due": p.due, "payload": {
            "model": f"adapter:{tenant_id(p.tenant)}", "prompt": p.prompt,
            "max_tokens": p.max_tokens, "stream": True}} for p in planned]}))
    proc = await asyncio.create_subprocess_exec(
        sys.executable, str(LOADGEN), str(sched_path), str(out_path))

    def snap():
        m = sched.metrics
        return {"rids": set(m.requests), "occ": len(m.occupancy),
                "queued": len(sched.queue)}

    try:
        await asyncio.sleep(max(0.0, w0 - time.monotonic()))
        first = await server.bridge.call(snap)
        with harness.profiled(cell, trace) as tdir:
            await asyncio.sleep(max(0.0, w1 - time.monotonic()))
            last = await server.bridge.call(snap)
        while proc.returncode is None:
            try:
                await asyncio.wait_for(proc.wait(), 5.0)
            except asyncio.TimeoutError:
                now = await server.bridge.call(snap)
                harness.log(f"draining: {len(sched.queue)} queued, "
                            f"{len(now['rids']) - len(first['rids'])} "
                            "requests since the window opened")
                if time.monotonic() > w1 + DRAIN_S + 30:
                    break
        rc = proc.returncode
        if rc != 0:
            raise harness.BenchError(f"load generator exited {rc}")
        results = json.loads(out_path.read_text())
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        shutil.rmtree(workdir, ignore_errors=True)
    m = sched.metrics
    new = last["rids"] - first["rids"]
    sched_stats = {
        "prime_s": [m.requests[r].prime_s for r in new
                    if m.requests[r].prime_s is not None],
        "occupancy": list(m.occupancy[first["occ"]:last["occ"]]),
        "queued": (first["queued"], last["queued"])}
    return results, w0, w1, sched_stats, tdir


def checked_sample(results, planned, w0, w1, eos, seed: int,
                   k: int) -> List[int]:
    """Indices of the finished window requests the reference checks: the
    one with the most served tokens and k - 1 others drawn from the seed."""
    done = [i for i, (r, p) in enumerate(zip(results, planned))
            if w0 <= r["due"] < w1 and _ok(r, p.max_tokens, eos)]
    if not done:
        return []
    longest = max(done, key=lambda i: len(results[i]["tokens"]))
    rest = [i for i in done if i != longest]
    rng = harness.np_rng(seed, 13)
    return [longest] + [int(i) for i in rng.choice(
        rest, min(len(rest), k - 1), replace=False)]


def max_logit_gap(cell: Cell, base, entries, coefs, served, control=None):
    """Widest gap, over every served position of `served` ((planned
    request, served tokens) pairs), by which the served token's reference
    logit lies below the reference's best, in standard deviations of that
    position's reference logits (so the number means the same at any
    width). With `control` (a matmul of
    bench.reference.dense_lm) the served tokens are replaced by what that
    lower precision puts first. -> (widest gap, tokens checked)"""
    import jax
    import jax.numpy as jnp
    from bench.reference import dense_lm

    arch = dense_lm.Arch(cell.config)
    ent = {s: jnp.asarray(v) for s, v in entries.items()}
    max_len = cell.config["serve"]["max_len"]

    def fwd(mm):
        return jax.jit(lambda base, ent, coefs, toks: dense_lm.logits(
            arch, base, ent, coefs, toks, mm))

    ref = fwd(dense_lm.matmul_f32)
    low = fwd(control) if control is not None else None
    worst, n_tok = float("-inf"), 0
    with jax.default_matmul_precision("highest"):
        for p, toks_out in served:
            seq = p.prompt + toks_out[:-1]
            toks = np.zeros((1, max_len), np.int32)
            toks[0, :len(seq)] = seq
            c = {s: jnp.asarray(v[p.tenant]) for s, v in coefs.items()}
            rows = np.asarray(ref(base, ent, c, jnp.asarray(toks))[0])
            rows = rows[len(p.prompt) - 1:len(seq)]
            pick = np.asarray(toks_out)
            if low is not None:
                lrows = np.asarray(low(base, ent, c, jnp.asarray(toks))[0])
                pick = lrows[len(p.prompt) - 1:len(seq)].argmax(-1)
            gaps = (rows.max(-1) - rows[np.arange(len(pick)), pick]) \
                / rows.std(-1)
            worst = max(worst, float(gaps.max()))
            n_tok += len(pick)
    return worst, n_tok


def build_server(cell: Cell, seed: int, devices, wrap_scheduler=None):
    """The gateway over the scheduler, engine and bank, with the
    benchmark's weights and tenants. -> (server, params, entries, coefs)"""
    import jax.numpy as jnp
    from repro.configs.base import PEFTConfig, ShapeConfig
    from repro.dist import plan as plan_mod
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.serve import AdapterBank, ContinuousScheduler, Engine
    from repro.serve.gateway import GatewayServer

    from bench import weights

    conf, mix, sv = cell.config, cell.traffic, cell.config["serve"]
    cfg = harness.model_config(conf)
    prof = harness.peft_config(conf)
    eos = sv.get("eos_token_id")
    model = build(cfg, PEFTConfig(method="none"))
    mesh = make_host_mesh(model=1, devices=devices[:1])
    plan = plan_mod.resolve("rules", model=model, mesh=mesh,
                            shape=ShapeConfig("serve", sv["max_len"],
                                              sv["slots"], "decode"),
                            workload="decode")
    harness.log(f"{cell.name}: {cfg.num_layers} layers, {sv['slots']} slots,"
                f" max_len {sv['max_len']}, {mix['tenants']} tenants")
    params, entries, coefs = weights.serve_params(
        model, prof, mesh, plan, seed, mix["tenants"],
        mix["tenant_delta_rms"])
    bank = AdapterBank(model, {prof.method: prof}, capacity=mix["tenants"])
    group = bank.params[prof.method]
    for site, uv in entries.items():       # the benchmark's entries
        group["aux"][site]["entries"] = jnp.asarray(uv)
    for i in range(mix["tenants"]):
        bank.load(tenant_id(i), {s: {"c": c[i]} for s, c in coefs.items()},
                  prof)
    engine = Engine(model, params, batch_slots=sv["slots"],
                    max_len=sv["max_len"], mesh=mesh, bank=bank, plan=plan)
    sched = ContinuousScheduler(engine, eos_id=eos, paged=True,
                                page_size=sv["page_size"],
                                n_pages=sv["n_pages"])
    if wrap_scheduler is not None:
        wrap_scheduler(sched)
    server = GatewayServer(sched, eos_id=eos, max_queue=sv["max_queue"],
                           default_max_new=16)
    return server, params, entries, coefs


def workdir_for(cell: Cell) -> Path:
    """A new directory for one session's load-generator files (a process
    of its own each, so that runs side by side never share one)."""
    base = cell.root / ".bench_run"
    base.mkdir(parents=True, exist_ok=True)
    return Path(tempfile.mkdtemp(prefix=f"{cell.name}.", dir=base))


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, devices, wrap_scheduler=None) -> RunResult:
    """`wrap_scheduler(sched)` plants a fault in the timed path (tests
    only)."""
    conf, mix, sv = cell.config, cell.traffic, cell.config["serve"]
    eos = sv.get("eos_token_id")
    server, params, entries, coefs = build_server(cell, seed, devices,
                                                  wrap_scheduler)
    policy = server.sched.model.explain_kernels()
    planned = traffic_gen.plan(mix, seconds, seed, server.vocab,
                               avoid_token=-1 if eos is None else eos)
    setup = {}

    async def main():
        await server.start("127.0.0.1", 0)
        try:
            await _warm(server, cell, planned, eos)
            setup["end"] = time.perf_counter()
            return await session(cell, server, planned, seconds, trace,
                                 workdir_for(cell))
        finally:
            await server.close()

    results, w0, w1, stats, tdir = asyncio.run(main())
    setup_s = setup["end"] - t_start
    em = window_metrics(results, planned, w0, w1, eos,
                        miss_at=w1 + DRAIN_S)
    harness.log(f"set-up {setup_s:.1f} s; window: {em}")
    mem = harness.peak_memory_bytes(devices[:1])
    base = params["base"]
    del server
    gc.collect()

    # the plain reference over a sample of the window's finished requests
    t0 = time.perf_counter()
    pick = checked_sample(results, planned, w0, w1, eos, seed,
                          mix["checked_requests"])
    worst, n_tok = max_logit_gap(cell, base, entries, coefs,
                                 [(planned[i], results[i]["tokens"])
                                  for i in pick])
    harness.log(f"reference {time.perf_counter() - t0:.1f} s over "
                f"{len(pick)} requests, {n_tok} served tokens")
    checks = [Check("logit_gap", worst, cell.limits["logit_gap"])]
    ctx = {"kind": "serve", "config": conf, "traffic": mix,
           "results": results, "planned": planned, "w0": w0, "w1": w1,
           "sched": stats, "window": em}
    return RunResult(
        attempted=em["attempted"], failed=em["failed"],
        end_to_end={"itl_p99_ms": em["itl_p99_ms"], "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem, context=ctx, trace_dir=tdir,
        notes=[f"kernel policy:\n{policy}"])
