"""OpenAI-compatible serving gateway launcher (DESIGN.md §Gateway):
`python -m repro.launch.api --arch <id> [...]`.

Boots the continuous-batching runtime (paged KV cache, optional adapter
bank and speculative decoding — the same flags as `repro.launch.serve
--continuous`) behind the asyncio HTTP gateway: `/v1/chat/completions`
and `/v1/completions` with SSE streaming, per-tenant adapter routing via
the `model` field (`adapter:<id>` names resolve through the bank, loading
non-resident tenants from `--bank-dir` checkpoints at admission),
backpressure 429s past `--max-queue`, and `/metrics` in Prometheus text.

`build_scheduler(args)` is importable: `benchmarks/loadgen.py --verify`
rebuilds the identical engine from the same CLI flags and replays the
collected traffic in-process to assert the gateway's streams were
bit-identical, and `bench_serve_gateway` boots in-process cells with it.

Laptop-scale demo:
    PYTHONPATH=src python -m repro.launch.api --arch yi-6b --reduced \
        --port 8080
    curl -N localhost:8080/v1/chat/completions -d '{"model": "base", \
        "messages": [{"role": "user", "content": "hi"}], "stream": true}'
"""
from __future__ import annotations

import argparse
import asyncio
import signal

import jax

import repro.configs as configs
from repro.configs.base import PEFTConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build


def add_model_args(ap: argparse.ArgumentParser) -> None:
    """Engine/scheduler flags, shared verbatim with `loadgen --verify` so
    the replay check rebuilds exactly the served model."""
    ap.add_argument("--arch", default="yi-6b", choices=list(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (continuous batch width)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--dense-cache", action="store_true",
                    help="dense per-slot KV cache instead of paged")
    ap.add_argument("--bank-dir", default=None,
                    help="adapter-only export dir: serve a multi-tenant "
                         "bank routed by model name (adapter:<id>)")
    ap.add_argument("--bank-capacity", type=int, default=8)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token (finish_reason 'stop'); default none")
    ap.add_argument("--speculative", action="store_true")
    ap.add_argument("--drafter", default="self", choices=("self", "ngram"))
    ap.add_argument("--draft-k", type=int, default=4)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="TP axis size; remaining devices replicate/batch")
    # tiered-memory serving (DESIGN.md §Tiering)
    ap.add_argument("--n-pages", type=int, default=None,
                    help="constrain the device page pool (default: enough "
                         "for every slot at max_len)")
    ap.add_argument("--preempt", action="store_true",
                    help="evict lower-class slots under pressure instead "
                         "of deferring higher-class admissions")
    ap.add_argument("--preempt-mode", default="auto",
                    choices=("auto", "swap", "recompute"),
                    help="victim KV disposition (auto = cost estimate)")
    ap.add_argument("--host-kv-pages", type=int, default=0,
                    help="host-RAM KV tier capacity in pages (0 disables): "
                         "swap-preempt snapshots and demoted prefix pages")
    ap.add_argument("--host-adapter-slots", type=int, default=0,
                    help="host-RAM adapter tier rows (0 disables): bank "
                         "evictions spill here; admission refills without "
                         "re-reading the checkpoint")


def _model_cfg(args):
    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg).replace(vocab=min(cfg.vocab, 512))
    return cfg


def export_demo_bank(args, directory: str) -> None:
    """Write two synthetic tenants (`t0` fourierft, `t1` lora) compatible
    with the model the flags build — gives the CI gateway smoke and laptop
    demos something to route at (`--models base,adapter:t0,adapter:t1`)
    without a training run."""
    import jax.numpy as jnp

    from repro.checkpoint import adapters as adapter_ckpt
    from repro.core import adapter as adapter_api
    from repro.core import peft as peft_mod

    model = build(_model_cfg(args), PEFTConfig(method="none"))
    profiles = {
        "fourierft": PEFTConfig(method="fourierft", n=16, alpha=25.0,
                                param_dtype="float32"),
        "lora": PEFTConfig(method="lora", lora_r=2, param_dtype="float32"),
    }
    for i, (tid, m) in enumerate(zip(("t0", "t1"), ("fourierft", "lora"))):
        prof = profiles[m]
        tree = peft_mod.init_adapters(
            jax.random.PRNGKey(args.seed + 10 + i), model.sites, prof)
        tree = jax.tree.map(
            lambda x: x + 0.05 if jnp.issubdtype(x.dtype, jnp.floating)
            else x, tree)
        trainable = set(adapter_api.resolve(m).trainable_leaves(prof))
        tree = {s: {k: v for k, v in d.items() if k in trainable}
                for s, d in tree.items()}
        adapter_ckpt.export_adapter(directory, tid, tree, prof)
    print(f"exported demo tenants "
          f"{adapter_ckpt.list_adapters(directory)} -> {directory}")


def build_scheduler(args):
    """(ContinuousScheduler, resident tenant ids) from parsed model args —
    deterministic in the flags: two builds from equal flags serve
    bit-identical streams (the gateway CI check leans on this)."""
    from repro.checkpoint import adapters as adapter_ckpt
    from repro.serve import (
        AdapterBank, ContinuousScheduler, Engine, NGramDrafter, SelfDrafter,
        TieringConfig,
    )

    from repro.dist import sharding as shd

    cfg = _model_cfg(args)
    model = build(cfg, PEFTConfig(method="none"))
    mesh = make_host_mesh(model=args.model_parallel)
    # weights are drawn under jit straight into the rules' placements
    params, _ = shd.init_placed(
        model.init, jax.random.PRNGKey(args.seed), mesh,
        lambda t: shd.state_specs(t, mesh, cfg))

    bank, tenant_ids = None, []
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
        for tid in tenant_ids:                 # warm the bank up front;
            if len(bank.resident_ids) >= args.bank_capacity:
                break                          # the rest load at admission
            try:
                bank.load_from_checkpoint(tid)
            except (ValueError, KeyError) as e:
                print(f"skipping tenant {tid!r}: {e}")

    engine = Engine(model, params, batch_slots=args.slots,
                    max_len=args.max_len, mesh=mesh, bank=bank)
    drafter = None
    if args.speculative:
        drafter = (SelfDrafter(k=args.draft_k) if args.drafter == "self"
                   else NGramDrafter(k=args.draft_k))
    tiering = None
    if args.preempt or args.host_kv_pages or args.host_adapter_slots:
        tiering = TieringConfig(host_kv_pages=args.host_kv_pages,
                                host_adapter_slots=args.host_adapter_slots,
                                preempt=args.preempt,
                                mode=args.preempt_mode)
    sched = ContinuousScheduler(engine, eos_id=args.eos_id,
                                paged=not args.dense_cache,
                                page_size=args.page_size,
                                n_pages=args.n_pages, drafter=drafter,
                                tiering=tiering)
    return sched, tenant_ids


async def _run(args) -> None:
    from repro.serve.gateway import GatewayServer

    sched, tenant_ids = build_scheduler(args)
    server = GatewayServer(
        sched, eos_id=args.eos_id, max_queue=args.max_queue,
        min_free_page_frac=args.min_free_page_frac,
        retry_after_s=args.retry_after,
        request_timeout_s=args.timeout,
        default_max_new=args.default_max_new)
    await server.start(args.host, args.port)
    print(f"gateway listening on {server.url} "
          f"({len(tenant_ids)} tenants, {sched.n_slots} slots, "
          f"max_len {sched.max_len})", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:            # non-unix event loops
            pass
    await stop.wait()
    print("gateway shutting down", flush=True)
    await server.close()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 picks an ephemeral port (printed at startup)")
    ap.add_argument("--max-queue", type=int, default=32,
                    help="queued-request watermark: at/above it new "
                         "requests get 429 + Retry-After")
    ap.add_argument("--min-free-page-frac", type=float, default=0.0,
                    help="page-pool watermark: with a non-empty queue and "
                         "less than this fraction free, 429 (0 disables)")
    ap.add_argument("--retry-after", type=float, default=1.0,
                    help="Retry-After seconds advertised on 429")
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-request deadline in seconds (cancels the "
                         "request mid-stream on overrun)")
    ap.add_argument("--default-max-new", type=int, default=16)
    ap.add_argument("--export-demo-bank", metavar="DIR", default=None,
                    help="write two synthetic tenants for the model flags "
                         "into DIR and exit (no server)")
    args = ap.parse_args(argv)
    setup_compile_cache()
    if args.export_demo_bank:
        export_demo_bank(args, args.export_demo_bank)
        return
    asyncio.run(_run(args))


if __name__ == "__main__":
    main()
