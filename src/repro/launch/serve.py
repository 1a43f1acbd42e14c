"""Serving launcher: `python -m repro.launch.serve --arch <id> [...]`.

Loads base weights (+ optional adapter checkpoint for ANY registered
`AdapterMethod`), merges every mergeable ΔW into the base (zero-latency
serving, paper §3.1), and decodes a batch of demo prompts through the slot
engine. With `--bank-dir`, instead serves a multi-tenant adapter bank: every
adapter-only export in the directory (checkpoint/adapters.py) is loaded
resident and the demo prompts round-robin over the tenants in one
heterogeneous batch.

With `--continuous`, replays a staggered-arrival, mixed-`max_new` traffic
trace through the continuous-batching scheduler (DESIGN.md §Scheduler)
instead of one lockstep batch: requests are admitted into slots as they
arrive (in-flight prefill over the live decode batch), every slot stops at
its own budget and is recycled immediately, and the run prints per-request
outputs plus serving metrics (TTFT, mean batch occupancy, tokens/s).
`--trace-n` sets the number of replayed requests and `--arrival-every`
their spacing on the decode-step clock; combine with `--bank-dir` to
replay multi-tenant traffic with LRU residency handled at admission, and
with `--speculative [--drafter self|ngram] [--draft-k K]` to decode
draft-then-verify (DESIGN.md §Speculation) and print acceptance metrics.

Laptop-scale demo:
    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --adapters /tmp/ft   # dir written by repro.launch.train
    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --bank-dir /tmp/tenants --bank-capacity 8
    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --continuous --trace-n 12 --arrival-every 2
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.checkpoint import adapters as adapter_ckpt
from repro.checkpoint import manager as ckpt
from repro.configs.base import PEFTConfig
from repro.core import adapter as adapter_api
from repro.dist import sharding as shd
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.serve import AdapterBank, Engine
from repro.train.step import join_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--method", default="fourierft",
                    choices=adapter_api.registered_methods())
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--alpha", type=float, default=300.0)
    ap.add_argument("--adapters", default=None,
                    help="checkpoint dir from repro.launch.train")
    ap.add_argument("--bank-dir", default=None,
                    help="adapter-only export dir: serve a multi-tenant bank")
    ap.add_argument("--bank-capacity", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--continuous", action="store_true",
                    help="replay a staggered-arrival trace through the "
                         "continuous-batching scheduler (slot recycling + "
                         "in-flight prefill) and print serving metrics")
    ap.add_argument("--trace-n", type=int, default=12,
                    help="--continuous: number of replayed requests")
    ap.add_argument("--arrival-every", type=float, default=2.0,
                    help="--continuous: arrival gap in decode steps")
    ap.add_argument("--dense-cache", action="store_true",
                    help="--continuous: dense per-slot KV cache instead of "
                         "the default paged cache (DESIGN.md §Paging)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--continuous: paged-cache page size (tokens)")
    ap.add_argument("--speculative", action="store_true",
                    help="--continuous: draft-then-verify speculative "
                         "decoding (DESIGN.md §Speculation); greedy outputs "
                         "stay token-identical to the plain loop")
    ap.add_argument("--drafter", default="self", choices=("self", "ngram"),
                    help="--speculative: base-row self-drafter (reuses the "
                         "bank's zero row) or host-side n-gram prompt lookup")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="--speculative: draft tokens per slot per step")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="--continuous: constrain the device page pool")
    ap.add_argument("--preempt", action="store_true",
                    help="--continuous: tiered scheduling — evict "
                         "lower-class slots under pressure (DESIGN.md "
                         "§Tiering)")
    ap.add_argument("--host-kv-pages", type=int, default=0,
                    help="--continuous: host-RAM KV tier pages (0 off)")
    ap.add_argument("--analyze", action="store_true",
                    help="--continuous: after the replay, audit the live "
                         "scheduler's jit signature counts against its "
                         "declared compile bounds (repro.analysis recompile "
                         "pass) and exit non-zero on any finding")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="TP axis size; remaining devices replicate/batch")
    ap.add_argument("--sharding-plan", default="rules",
                    help="rules|search|<plan.json>: where placements come "
                         "from (dist/plan.py); search runs the planner once "
                         "at startup")
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg).replace(vocab=min(cfg.vocab, 512))
    # bank-only serving runs over the clean base: random-init adapters of a
    # live method would otherwise be merged into it before the bank attaches
    if args.bank_dir and not args.adapters:
        peft = PEFTConfig(method="none")
    else:
        peft = PEFTConfig(method=args.method, n=args.n, alpha=args.alpha)
    model = build(cfg, peft)
    mesh = make_host_mesh(model=args.model_parallel)
    # weights are drawn under jit straight into their mesh placements (the
    # Engine re-places the merged tree per its own plan)
    params, _ = shd.init_placed(
        model.init, jax.random.PRNGKey(args.seed), mesh,
        lambda t: shd.state_specs(t, mesh, cfg))
    if args.adapters:
        state, at = ckpt.restore(args.adapters)
        trainable = state["trainable"]
        _, frozen = __import__("repro.train.step", fromlist=["split_params"]) \
            .split_params(model, params)
        params = join_params(model, trainable, frozen)
        print(f"loaded adapters from step {at}")

    bank = None
    tenant_ids = []
    if args.bank_dir:
        tenant_ids = list(adapter_ckpt.list_adapters(args.bank_dir))
        if not tenant_ids:
            raise SystemExit(f"no adapter exports under {args.bank_dir}")
        profiles = {}
        for tid in tenant_ids:
            tp = adapter_ckpt.read_manifest(args.bank_dir, tid)
            profiles.setdefault(tp.method, tp)
        bank = AdapterBank(model, profiles, capacity=args.bank_capacity,
                           checkpoint_dir=args.bank_dir)
        for tid in tenant_ids:
            if len(bank.resident_ids) >= args.bank_capacity:
                break
            try:
                bank.load_from_checkpoint(tid)
            except (ValueError, KeyError) as e:
                # e.g. same method exported under a different n/seed than the
                # group profile — serve the compatible tenants, don't die
                print(f"skipping tenant {tid!r}: {e}")
        if not bank.resident_ids:
            raise SystemExit("no loadable tenants for the bank profiles")
        tenant_ids = list(bank.resident_ids)   # demo serves residents only
        print(f"bank: {len(tenant_ids)} resident tenants over "
              f"groups {sorted(bank.profiles)}")

    slots = max(2, len(tenant_ids)) if bank else 2
    engine = Engine(model, params, batch_slots=slots, max_len=args.max_len,
                    mesh=mesh, bank=bank, plan=args.sharding_plan)
    prompts = [(jnp.arange(4 + i, dtype=jnp.int32) + 3 * i) % cfg.vocab
               for i in range(slots)]
    if cfg.n_codebooks:
        prompts = [jnp.tile(p[:, None], (1, cfg.n_codebooks)) for p in prompts]
    if args.continuous:
        from repro.serve import (
            ContinuousScheduler, NGramDrafter, SelfDrafter, TieringConfig,
        )
        from repro.serve.engine import Request
        drafter = None
        if args.speculative:
            drafter = (SelfDrafter(k=args.draft_k) if args.drafter == "self"
                       else NGramDrafter(k=args.draft_k))
        tiering = None
        if args.preempt or args.host_kv_pages:
            tiering = TieringConfig(host_kv_pages=args.host_kv_pages,
                                    preempt=args.preempt)
        sched = ContinuousScheduler(engine, paged=not args.dense_cache,
                                    page_size=args.page_size,
                                    n_pages=args.n_pages,
                                    drafter=drafter, tiering=tiering)
        n = args.trace_n
        reqs = [Request(prompt=prompts[i % len(prompts)],
                        max_new=1 + (5 * i + 3) % args.max_new,
                        adapter_id=(tenant_ids[i % len(tenant_ids)]
                                    if tenant_ids else None))
                for i in range(n)]
        arrivals = [i * args.arrival_every for i in range(n)]
        sched.serve(reqs, arrivals)
        for i, r in enumerate(reqs):
            tag = f" [{r.adapter_id}]" if r.adapter_id else ""
            print(f"request {i}{tag} (arrival {arrivals[i]:g}, "
                  f"max_new {r.max_new}): {r.out}")
        s = sched.metrics.summary()
        print(f"continuous: {s['n_requests']:.0f} requests, "
              f"{s['total_tokens']:.0f} tokens in {s['steps']:.0f} steps | "
              f"occupancy {s['occupancy_mean']:.2f}, "
              f"ttft {s['ttft_steps_mean']:.1f} steps (p90 "
              f"{s['ttft_steps_p90']:.1f}), "
              f"{s['tokens_per_s']:.0f} tok/s")
        if "attn_pages_live_frac" in s:
            print(f"paged attention: {s['attn_pages_live_frac']:.1%} of the "
                  f"kernel's page grid visited per step")
        if "spec_accept_rate" in s:
            print(f"speculative ({args.drafter}, k={args.draft_k}): "
                  f"{s['spec_tokens_per_step']:.2f} tokens/step/slot, "
                  f"accept rate {s['spec_accept_rate']:.2f}, "
                  f"{s['spec_drafts_wasted']:.0f} drafts wasted over "
                  f"{s['spec_slot_steps']:.0f} slot-steps")
        if args.analyze:
            from repro.analysis import hlo_lint
            found = hlo_lint.scheduler_recompile_findings(sched)
            sigs = sched.compiled_signatures()
            print("analyze: compiled signatures "
                  + ", ".join(f"{k}={v}" for k, v in sorted(sigs.items())))
            for f in found:
                print(f.render())
            if found:
                raise SystemExit(1)
            print("analyze: recompile audit clean")
        return

    ids = [tenant_ids[i % len(tenant_ids)] if tenant_ids else None
           for i in range(slots)] if bank else None
    outs = engine.generate(prompts, max_new=args.max_new, adapter_ids=ids)
    for i, o in enumerate(outs):
        tag = f" [{ids[i]}]" if ids else ""
        print(f"prompt {i}{tag}: {o.tolist()}")


if __name__ == "__main__":
    main()
