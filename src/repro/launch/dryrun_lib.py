"""Dry-run library: lower + compile every (arch × shape × mesh) cell with full
production shardings, extract memory / cost / collective analyses, and derive
the roofline terms (DESIGN §9).

Importable without touching jax device state — `launch/dryrun.py` (the script)
sets XLA_FLAGS for 512 host devices before importing this.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.configs.base import ModelConfig, PEFTConfig, ShapeConfig, TrainConfig
from repro.dist import hlo as hlo_mod
from repro.dist import plan as plan_mod
from repro.dist import sharding as shd
from repro.dist.sharding import axis_size
from repro.models.registry import Model, build
from repro.train import step as train_step_mod

# TPU v5e per-chip constants (assignment brief)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s per link (pessimistic single-link charge)
HBM_BYTES = 16e9           # v5e HBM capacity

ACT_BUDGET_BYTES = 4e9     # per-device activation-boundary budget for auto-microbatch


def long_context_skip(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    """long_500k runs only for sub-quadratic (SSM/hybrid) archs."""
    return shape.name == "long_500k" and not cfg.subquadratic


def auto_microbatch(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> int:
    """Pick gradient-accumulation factor so the per-device scan-boundary
    activation set (L · B_mb_local · S · d · 2B) fits the budget."""
    baxes = shd.batch_axes(mesh, shape.global_batch)
    nshard = int(np.prod([shd.axis_size(mesh, a) for a in baxes])) or 1
    b_loc = shape.global_batch // nshard
    budget = ACT_BUDGET_BYTES / (2 if cfg.moe is not None else 1)
    per_mb = lambda k: (cfg.num_layers * max(b_loc // k, 1) * shape.seq_len
                        * cfg.d_model * 2)
    k = 1
    while k < b_loc and per_mb(k) > budget:
        k *= 2
    return 0 if k == 1 else k


def _strip_axis(spec: P, axis: str) -> P:
    """Drop one mesh axis from a PartitionSpec (the gathered copy of an
    fsdp-scattered weight loses its `data` shard)."""
    out = []
    for e in tuple(spec):
        if e == axis:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a != axis)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(e)
    return P(*out)


def make_constrain(mesh: Mesh, cfg: ModelConfig, fsdp: bool = False,
                   source: Optional[plan_mod.PlanSource] = None):
    """Sharding-constraint hook: (a) merged ΔW stacks pinned to the weight's
    storage spec (whatever the resolved plan `source` chose — rules when
    None); (b) under FSDP, per-layer weight slices gathered over `data`
    inside the layer loop ("fsdp_gather/<name>" paths)."""
    source = source or plan_mod.RulesSource()
    # sequence-parallel residual stream: shard S over `model` at layer
    # boundaries for large-d archs. The remat boundary saves (L, B_mb, S, d)
    # then shard 16x (qwen2-vl-72b: 5.4GB -> 0.34GB per stack per device);
    # the TP all-reduce after wo/wo_mlp becomes reduce-scatter + all-gather
    # (same bytes), and norms run on S/16 shards.
    # scoped to qwen2-vl-72b: smaller archs fit without SP, and GSPMD-auto
    # SP costs extra reshard collectives (proper manual SP via shard_map is
    # the identified next step; see DESIGN.md §Dist)
    seq_parallel = cfg.d_model >= 8000

    def constrain(path: str, x):
        if path == "moe/dispatch":
            # 2-D expert-parallel: sequences over `data`, experts over
            # `model`. (E-only sharding leaves capacity global -> 16x
            # redundant expert FLOPs; global-capacity 2-D needs an
            # all-layout scatter -> 200s collectives. Measured, olmoe.)
            bax = shd.batch_axes(mesh, x.shape[0])
            spec = P(bax if bax else None,
                     shd._maybe(x.shape[1], mesh, "model"), None, None)
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        if path == "moe/tokens":
            bax = shd.batch_axes(mesh, x.shape[0])
            spec = P(bax if bax else None, None, None)
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        if path.startswith("act/"):
            # activations at layer boundaries: (B, S, d) batch-sharded,
            # everything else replicated. Without this anchor GSPMD's scan
            # fixpoint settles on partially-replicated activations
            # (measured: 8x redundant projection flops on yi-6b).
            bax = shd.batch_axes(mesh, x.shape[0])
            sax = ("model" if (seq_parallel and x.ndim == 3
                               and x.shape[1] % axis_size(mesh, "model") == 0
                               and x.shape[1] > 1) else None)
            spec = P(bax if bax else None, sax,
                     *([None] * (x.ndim - 2)))
        elif path.startswith("fsdp_gather/"):
            if not fsdp:
                return x
            spec = _strip_axis(
                source.param_spec(path[len("fsdp_gather/"):], x.shape, mesh,
                                  cfg, fsdp=False), "data")
        else:
            spec = source.param_spec(path, x.shape, mesh, cfg, fsdp=fsdp)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return constrain


def peft_for(cfg: ModelConfig, kind: str) -> PEFTConfig:
    """train: the paper's technique (n=1000, merged). serve: adapters merged
    offline (method none) except hybrid shared-block adapters (factored by
    construction)."""
    if kind == "train":
        # (strategy note, DESIGN §2: factored costs 4n(d1+d2) vs merged's
        # 2·d1·d2 per token — but under full remat the factored path is
        # recomputed 3x while merged's dW_eff GEMM runs once; measured on
        # qwen2-vl-72b train: factored = +52% compute, no memory win.
        # merged stays the default.)
        return PEFTConfig(method="fourierft", n=1000, alpha=300.0,
                          strategy="merged")
    if cfg.family == "hybrid":
        return PEFTConfig(method="fourierft", n=1000, alpha=300.0,
                          strategy="factored")
    return PEFTConfig(method="none")


@dataclasses.dataclass
class Cell:
    arch: str
    shape: ShapeConfig
    model: Model
    step_fn: object
    args: Tuple            # abstract args (ShapeDtypeStruct trees)
    in_shardings: Tuple
    donate: Tuple[int, ...]
    plan_source: Optional[plan_mod.PlanSource] = None


def build_cell(arch: str, shape_name: str, mesh: Mesh,
               *, peft: Optional[PEFTConfig] = None,
               remat: str = "full",
               microbatch: Optional[int] = None,
               sharding_plan: Optional[str] = None) -> Cell:
    """sharding_plan: rules|search|<plan.json> (or an already-resolved
    PlanSource) — which source places every tree of this cell."""
    cfg = configs.get(arch)
    shape = configs.shape_for(shape_name)
    fsdp = shd.fsdp_default(cfg, mesh)
    if long_context_skip(cfg, shape):
        raise ValueError(f"{arch} skips {shape_name} (full attention; see "
                         "DESIGN.md §Arch-applicability)")
    workload = shape.kind if shape.kind != "train" else "train"
    if shape.kind == "train":
        p = peft or peft_for(cfg, "train")
        model = build(cfg, p, remat=remat)
        src = (sharding_plan if isinstance(sharding_plan, plan_mod.PlanSource)
               else plan_mod.resolve(sharding_plan, model=model, mesh=mesh,
                                     shape=shape, workload=workload))
        model.constrain = make_constrain(mesh, cfg, fsdp, source=src)
        tcfg = TrainConfig(microbatch=(auto_microbatch(cfg, shape, mesh)
                                       if microbatch is None else microbatch))
        tstep = train_step_mod.make_train_step(model, tcfg)
        state, frozen = jax.eval_shape(
            lambda: train_step_mod.init_state(model, tcfg,
                                              jax.random.PRNGKey(0)))
        batch = model.input_specs(shape)
        state_sh = shd.named(state, src.state_specs(state, mesh, cfg, fsdp), mesh)
        frozen_sh = shd.named(frozen, src.state_specs(frozen, mesh, cfg, fsdp), mesh)
        batch_sh = shd.named(batch, src.batch_specs(batch, mesh, shape), mesh)
        return Cell(arch, shape, model, tstep, (state, frozen, batch),
                    (state_sh, frozen_sh, batch_sh), (0,), src)
    p = peft or peft_for(cfg, "serve")
    model = build(cfg, p, remat="none")
    src = (sharding_plan if isinstance(sharding_plan, plan_mod.PlanSource)
           else plan_mod.resolve(sharding_plan, model=model, mesh=mesh,
                                 shape=shape, workload=workload))
    model.constrain = make_constrain(mesh, cfg, fsdp, source=src)
    if shape.kind == "prefill":
        def prefill_step(params, batch):
            logits, _ = model.forward(params, batch)
            return logits[:, -1].astype(jnp.float32)

        params = model.init_shapes()
        batch = model.input_specs(shape)
        params_sh = shd.named(params, src.state_specs(params, mesh, cfg, fsdp), mesh)
        batch_sh = shd.named(batch, src.batch_specs(batch, mesh, shape), mesh)
        return Cell(arch, shape, model, prefill_step, (params, batch),
                    (params_sh, batch_sh), (), src)

    # decode
    def serve_step(params, cache, batch):
        return model.decode_step(params, cache, batch)

    params = model.init_shapes()
    cache = model.cache_specs(shape)
    batch = model.input_specs(shape)
    params_sh = shd.named(params, src.state_specs(params, mesh, cfg, fsdp), mesh)
    cache_sh = shd.named(cache, src.cache_specs(cache, mesh, cfg, shape), mesh)
    batch_sh = shd.named(batch, src.batch_specs(batch, mesh, shape), mesh)
    return Cell(arch, shape, model, serve_step, (params, cache, batch),
                (params_sh, cache_sh, batch_sh), (1,), src)


def lower_cell(cell: Cell):
    jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                     donate_argnums=cell.donate)
    return jitted.lower(*cell.args)


# ---------------------------------------------------------------------------
# Accounting
# ---------------------------------------------------------------------------

def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "size"))


def backbone_params(model: Model) -> Tuple[int, int]:
    """(N_total_backbone, N_active_backbone) — excludes embed/lm_head."""
    shapes = jax.eval_shape(
        lambda: model._mod.init_params(jax.random.PRNGKey(0), model.cfg))
    total = active = 0
    cfg = model.cfg
    for path, leaf in _walk(shapes):
        last = path.split("/")[-1]
        if last in ("embed", "lm_head"):
            continue
        n = int(np.prod(leaf.shape))
        total += n
        if last.startswith("we_") and cfg.moe is not None:
            active += n * cfg.moe.top_k // cfg.moe.num_experts
        else:
            active += n
    return total, active


def _walk(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk(v, f"{prefix}{k}/")
    else:
        yield prefix[:-1], tree


def model_flops(model: Model, shape: ShapeConfig) -> float:
    """Useful-work convention: 6·N_active·tokens (train), 2·N_active·tokens
    (prefill/decode forward)."""
    _, n_active = backbone_params(model)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # decode: 1 token/seq


def analyze(cell: Cell, lowered, compiled, mesh: Mesh,
            compile_seconds: float) -> Dict:
    chips = mesh.devices.size
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    # NOTE: XLA's cost_analysis visits while bodies once (no trip-count
    # scaling) -- useless for scanned programs. We re-derive from the HLO
    # with full call-graph multiplicity (dist/hlo.py) and keep XLA's numbers
    # for reference.
    stats = hlo_mod.analyze_module(compiled.as_text())
    flops_dev = float(stats.flops)
    bytes_dev = float(stats.bytes_min)
    bytes_dev_upper = float(stats.bytes)
    coll_dev = float(stats.collective_bytes)

    t_compute = flops_dev / PEAK_FLOPS
    # memory term uses the TPU-fusion-ideal bound (elementwise chains fused);
    # the CPU-fusion-granularity upper bound is reported alongside.
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "memory_s_upper": bytes_dev_upper / HBM_BW,
             "collective_s": t_coll}
    dominant = max(
        {k: terms[k] for k in ("compute_s", "memory_s", "collective_s")},
        key=terms.get)

    mf = model_flops(cell.model, cell.shape)
    useful_ratio = mf / (flops_dev * chips) if flops_dev else 0.0
    bound = max(t_compute, t_memory, t_coll)
    ideal = mf / (chips * PEAK_FLOPS)
    roofline_frac = ideal / bound if bound > 0 else 0.0

    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # provenance + predicted cost of whichever plan source placed this cell
    # (what BENCH_analysis.json's sharding_plan_* rows correlate against the
    # analyzer terms above)
    plan_info = None
    if cell.plan_source is not None:
        plan_info = dict(cell.plan_source.describe())
        try:
            from repro.dist import planner
            plan_info["predicted"] = planner.score_source(
                cell.model, mesh, cell.shape, cell.plan_source).to_json()
        except Exception as e:               # prediction must never sink a run
            plan_info["predicted_error"] = f"{type(e).__name__}: {e}"
    return {
        "arch": cell.arch,
        "shape": cell.shape.name,
        "kind": cell.shape.kind,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "chips": chips,
        "flops_per_device": flops_dev,
        "dot_flops_per_device": float(stats.dot_flops),
        "bytes_per_device": bytes_dev,
        "bytes_per_device_upper": bytes_dev_upper,
        "collective_bytes_per_device": coll_dev,
        "collectives": stats.bytes_by_kind,
        "collective_counts": stats.count_by_kind,
        "xla_cost_analysis": {
            "flops_unscaled": float(cost.get("flops", 0.0)),
            "bytes_unscaled": float(cost.get("bytes accessed", 0.0)),
        },
        "terms": terms,
        "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": useful_ratio,
        "roofline_fraction": roofline_frac,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": peak,
            "fits_hbm": bool(peak < HBM_BYTES),
        },
        "compile_seconds": compile_seconds,
        "sharding_plan": plan_info,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None, *,
             peft: Optional[PEFTConfig] = None,
             variant: str = "baseline",
             remat: str = "full",
             microbatch: Optional[int] = None,
             mesh_shape: Optional[str] = None,
             save_hlo: bool = False,
             sharding_plan: Optional[str] = None) -> Dict:
    """mesh_shape: optional "DxM" remap of the same chips (perf variants);
    the required dry-run meshes stay (16,16) / (2,16,16).
    sharding_plan: rules|search|<plan.json> — plan source for every tree."""
    from repro.launch.mesh import (
        make_mesh, make_production_mesh, parse_mesh_shape)
    if mesh_shape:
        dims, axes = parse_mesh_shape(mesh_shape)
        mesh = make_mesh(dims, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape_name, mesh, peft=peft, remat=remat,
                      microbatch=microbatch, sharding_plan=sharding_plan)
    t0 = time.time()
    with mesh:
        lowered = lower_cell(cell)
        compiled = lowered.compile()
    dt = time.time() - t0
    result = analyze(cell, lowered, compiled, mesh, dt)
    result["variant"] = variant
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
        if variant != "baseline":
            tag += f"__{variant}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1)
        if save_hlo:
            with open(os.path.join(out_dir, tag + ".hlo.txt"), "w") as f:
                f.write(compiled.as_text())
    return result
