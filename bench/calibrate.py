#!/usr/bin/env python3
"""Readings that set a cell's limits (bench/limits/<cell>.json): what the
control and each planted fault give on the compared numbers, on the chip,
at the cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 30]

Training cells: for each seed, the plain reference in float32 stands for a
sound program, and is compared, number by number, with
  - control: the same reference with every model matmul in float8 (e4m3,
    per-tensor scale), the precision below the configuration's bfloat16;
  - half_batch: the reference taking its loss over half of each batch.
(A step that returns its state unchanged reads exactly 1 on `change`.)
Serving cells: for each seed, one run of the cell at its own load, then the
widest logit gap of the served tokens (the program's reading) and of the
tokens the float8 control puts first at the same positions.

Prints one JSON line per seed and reading. The benchmark's runs never call
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def train_readings(cell, seed: int, devices):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build

    from bench import harness, weights
    from bench.drivers import train
    from bench.reference import dense_lm

    conf, tr, tc = cell.config, cell.traffic, cell.config["train"]
    model = build(harness.model_config(conf), harness.peft_config(conf),
                  remat=tc["remat"])
    n_dev = tc["mesh"]["data"] * tc["mesh"]["model"]
    mesh = make_host_mesh(model=tc["mesh"]["model"], devices=devices[:n_dev])
    tcfg = TrainConfig(learning_rate=tr["learning_rate"],
                       warmup_steps=tr["warmup_steps"],
                       schedule=tr["schedule"], grad_clip=tr["grad_clip"])
    state, frozen, _, _, entries = weights.train_state(model, tcfg, mesh,
                                                       seed)
    coefs0 = train._host(train._site_coefs(state["trainable"]))
    del state
    gc.collect()
    B, S = tc["batch"], tr["seq_len"]
    feed = train.TokenFeed(model.cfg.vocab, B, S, seed)
    arch = dense_lm.Arch(conf)
    hp = {k: tr[k] for k in ("learning_rate", "warmup_steps", "grad_clip")}
    ent = {s: jnp.asarray(v) for s, v in entries.items()}
    c0 = {s: jnp.asarray(v) for s, v in coefs0.items()}

    def batches(rows):
        return [(jnp.asarray(b["tokens"][:rows]),
                 jnp.asarray(b["labels"][:rows]))
                for b in (feed.batch_at(i)
                          for i in range(tr["checked_steps"]))]

    def steps(mm, rows):
        with jax.default_matmul_precision("highest"):
            losses, g1, c = dense_lm.train(arch, hp, c0, frozen["base"], ent,
                                           batches(rows), mm)
        return losses, train._host(g1), train._host(c)

    ref = steps(dense_lm.matmul_f32, B)
    out = {}
    for name, mm, rows in (("control", dense_lm.matmul_fp8, B),
                           ("half_batch", dense_lm.matmul_f32, B // 2)):
        got = steps(mm, rows)
        out[name] = {
            "loss": max(abs(a - b) / abs(b) for a, b in zip(got[0], ref[0])),
            "grad1": train.leaf_norm_gap(got[1], ref[1]),
            "change": train.leaf_norm_gap(
                {s: got[2][s] - coefs0[s] for s in coefs0},
                {s: ref[2][s] - coefs0[s] for s in coefs0})}
    return out


def serve_readings(cell, seed: int, seconds: float, devices):
    import asyncio

    from bench import traffic_gen
    from bench.drivers import serve
    from bench.reference import dense_lm

    eos = cell.config["serve"].get("eos_token_id")
    server, params, entries, coefs = serve.build_server(cell, seed, devices)
    planned = traffic_gen.plan(cell.traffic, seconds, seed, server.vocab,
                               avoid_token=-1 if eos is None else eos)

    async def main():
        await server.start("127.0.0.1", 0)
        try:
            await serve._warm(server, cell, planned, eos)
            return await serve.session(cell, server, planned, seconds, False,
                                       serve.workdir_for(cell))
        finally:
            await server.close()

    results, w0, w1, _, _ = asyncio.run(main())
    base = params["base"]
    del server
    gc.collect()
    pick = serve.checked_sample(results, planned, w0, w1, eos, seed,
                                cell.traffic["checked_requests"])
    served = [(planned[i], results[i]["tokens"]) for i in pick]
    prog, n = serve.max_logit_gap(cell, base, entries, coefs, served)
    ctrl, _ = serve.max_logit_gap(cell, base, entries, coefs, served,
                                  control=dense_lm.matmul_fp8)
    return {"program": {"logit_gap": prog, "tokens": n},
            "control": {"logit_gap": ctrl, "tokens": n}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    devices = harness.require_tpu(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cell.traffic["kind"] == "train":
            out = train_readings(cell, seed, devices)
        else:
            out = serve_readings(cell, seed, args.seconds, devices)
        for name, vals in out.items():
            print(json.dumps({"seed": seed, "reading": name, **vals}),
                  flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.0f} s",
              file=sys.stderr, flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
