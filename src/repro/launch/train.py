"""Training launcher: `python -m repro.launch.train --arch <id> [...]`.

Runs the fault-tolerant FourierFT fine-tuning loop on the local device(s).
On a real fleet the same entrypoint runs per host under the cluster launcher
(jax.distributed.initialize is a no-op single-host); the data pipeline is
step-keyed so any host can (re)compute its shard for any step, and
`--resume auto` picks up the newest checkpoint after preemption/restart.

Laptop-scale demo:
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --steps 100 --ckpt-dir /tmp/ft --method fourierft --n 128
"""
from __future__ import annotations

import argparse

import jax

import repro.configs as configs
from repro.configs.base import PEFTConfig, TrainConfig
from repro.core import adapter as adapter_api
from repro.data import SyntheticLM
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.train import loop, step as train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-runnable)")
    ap.add_argument("--method", default="fourierft",
                    choices=adapter_api.registered_methods())
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--alpha", type=float, default=300.0)
    ap.add_argument("--lora-r", type=int, default=8)
    ap.add_argument("--strategy", default="merged",
                    choices=["merged", "factored"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--task-seed", type=int, default=7)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="TP axis size; remaining devices form `data`")
    ap.add_argument("--fsdp", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="--fsdp forces FSDP on, --no-fsdp off; default "
                         "auto per dist.sharding.fsdp_default")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg).replace(vocab=min(cfg.vocab, 512))
    peft = PEFTConfig(method=args.method, n=args.n, alpha=args.alpha,
                      lora_r=args.lora_r, strategy=args.strategy)
    model = build(cfg, peft, remat=args.remat)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 10, 1),
                       microbatch=args.microbatch, seed=args.seed,
                       grad_compression=args.grad_compression)
    # everything routes through the mesh path: a 1x1 host mesh degenerates to
    # the single-device behavior, larger device counts shard for free
    mesh = make_host_mesh(model=args.model_parallel)
    print(f"arch={cfg.name} method={args.method} "
          f"mesh={'x'.join(map(str, mesh.devices.shape))} "
          f"trainable={model.trainable_params():,}")
    fsdp = args.fsdp                       # None = auto
    data = SyntheticLM(vocab=cfg.vocab, batch=args.batch, seq=args.seq,
                       seed=args.seed, task_seed=args.task_seed,
                       codebooks=cfg.n_codebooks)
    state, frozen = train_step.init_state(model, tcfg,
                                          jax.random.PRNGKey(args.seed),
                                          mesh=mesh, fsdp=fsdp)
    state, frozen, state_sh, frozen_sh = train_step.shard_train_state(
        model, state, frozen, mesh, fsdp=fsdp)
    step_fn, batch_sh = train_step.make_sharded_train_step(
        model, tcfg, mesh, state, frozen, data.batch_at(0),
        shardings=(state_sh, frozen_sh))
    state, report = loop.run(
        step_fn, state, frozen, data, tcfg, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
        resume=not args.no_resume, log_every=max(args.steps // 20, 1),
        mesh=mesh, batch_sharding=batch_sh, state_sharding=state_sh)
    print(f"done: steps={report.steps_run} final_loss={report.final_loss:.4f} "
          f"anomalies={report.anomalies} slow_steps={report.slow_steps}"
          + (f" (resumed from {report.resumed_from})"
             if report.resumed_from else ""))


if __name__ == "__main__":
    main()
