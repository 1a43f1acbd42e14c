"""Train-step factory: PEFT-filtered gradients, microbatch accumulation,
anomaly-guarded updates.

Parameters are split into (trainable, frozen): gradients are taken w.r.t. the
trainable subtree only, so XLA dead-code-eliminates every frozen-weight
gradient GEMM — the structural memory/compute win of PEFT. The frozen subtree
is passed as a separate argument (not captured) so the sharded step can place
and donate it explicitly.

Anomaly guard (fault tolerance): non-finite or exploding loss/grad-norm skips
the update (params/opt unchanged) and increments `anomalies` in the state —
on real fleets this absorbs bit-flip/overflow steps without killing the run.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.core.peft import trainable_adapter_tree
from repro.models.registry import Model
from repro.optim import adamw, schedules


def split_params(model: Model, params: Dict) -> Tuple[Dict, Dict]:
    """-> (trainable, frozen). frozen = {"base":..., "peft":... (frozen leaves)}.
    The trainable/frozen boundary inside each adapter dict comes from the
    method's `trainable_leaves` protocol (core/adapter.py)."""
    peft = model.peft
    if model.method.trains_base:
        trainable = {"base": params["base"]}
        frozen = {"base": {}, "peft": {}}
        return trainable, frozen
    trainable: Dict = {"peft": trainable_adapter_tree(params["peft"], peft)}
    frozen_adapters = {
        site: {k: v for k, v in d.items()
               if k not in trainable["peft"].get(site, {})}
        for site, d in params["peft"].items()
    }
    base = params["base"]
    if peft.train_head:
        base = dict(base)
        trainable["head"] = base.pop("lm_head")
    return trainable, {"base": base, "peft": frozen_adapters}


def join_params(model: Model, trainable: Dict, frozen: Dict) -> Dict:
    if model.method.trains_base:
        return {"base": trainable["base"], "peft": {}}
    base = frozen["base"]
    if "head" in trainable:
        base = dict(base)
        base["lm_head"] = trainable["head"]
    peft_tree = {
        site: {**frozen["peft"].get(site, {}),
               **trainable.get("peft", {}).get(site, {})}
        for site in set(frozen["peft"]) | set(trainable.get("peft", {}))
    }
    return {"base": base, "peft": peft_tree}


def init_state(model: Model, tcfg: TrainConfig, rng: jax.Array, mesh=None,
               fsdp: bool = None) -> Tuple[Dict, Dict]:
    """-> (state, frozen). state = {step, trainable, opt, loss_ema, anomalies}
    (+ ef_residual when int8 error-feedback grad compression is on).

    With `mesh`, the pair is built under jit straight into the placements
    `shard_train_state` gives it (same `fsdp`), so no device ever
    holds the whole tree on the way there."""
    def build(rng):
        params = model.init(rng)
        trainable, frozen = split_params(model, params)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "trainable": trainable,
            "opt": adamw.init(trainable),
            "loss_ema": jnp.zeros((), jnp.float32),
            "anomalies": jnp.zeros((), jnp.int32),
        }
        if tcfg.grad_compression == "int8_ef":
            from repro.dist import compression
            state["ef_residual"] = compression.init_residual(trainable)
        return state, frozen

    if mesh is None:
        return build(rng)
    from repro.dist import sharding as shd
    if fsdp is None:
        fsdp = shd.fsdp_default(model.cfg, mesh)
    specs = lambda t: shd.state_specs(t, mesh, model.cfg, fsdp)
    pair, _ = shd.init_placed(build, rng, mesh,
                              lambda t: (specs(t[0]), specs(t[1])))
    return pair


def _loss_for(model: Model):
    if model.method.trains_base:
        def loss_f(trainable, frozen, batch):
            return model.loss({"base": trainable["base"], "peft": {}}, batch)
    else:
        def loss_f(trainable, frozen, batch):
            return model.loss_from_parts(trainable, frozen["base"],
                                         frozen["peft"], batch)
    return loss_f


def make_train_step(model: Model, tcfg: TrainConfig) -> Callable:
    # ΔW materialization inside the step dispatches through the kernel
    # registry (merge_site -> site_delta -> KernelOp, DESIGN.md §Kernels);
    # fail fast here — before any tracing — if the model's build-time policy
    # left a (site, op) pair without a usable backend.
    model.kernel_policy.validate()
    loss_f = _loss_for(model)

    def grads_of(trainable, frozen, batch):
        if tcfg.microbatch and tcfg.microbatch > 0:
            k = tcfg.microbatch

            def resh(key, x):
                if key == "positions" and x.ndim == 3:   # (3, B, S) m-rope
                    return x.reshape((3, k, x.shape[1] // k)
                                     + x.shape[2:]).swapaxes(0, 1)
                return x.reshape((k, x.shape[0] // k) + x.shape[1:])

            mb = {kk: resh(kk, v) for kk, v in batch.items()}

            def acc(carry, mbatch):
                l, g = jax.value_and_grad(loss_f)(trainable, frozen, mbatch)
                loss_acc, grad_acc = carry
                return (loss_acc + l,
                        jax.tree.map(jnp.add, grad_acc, g)), None

            zero = (jnp.float32(0.0),
                    jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                                 trainable))
            (loss, grads), _ = jax.lax.scan(acc, zero, mb)
            scale = 1.0 / k
            return loss * scale, jax.tree.map(lambda g: g * scale, grads)
        return jax.value_and_grad(loss_f)(trainable, frozen, batch)

    compress = tcfg.grad_compression == "int8_ef"
    if compress:
        from repro.dist import compression

    def train_step(state: Dict, frozen: Dict, batch: Dict):
        loss, grads = grads_of(state["trainable"], frozen, batch)
        if compress:
            # what the cross-pod all-reduce would transport: int8 + carried
            # quantization residual (dist/compression.py)
            grads, new_residual = compression.compress_with_feedback(
                grads, state["ef_residual"])
        grads, gnorm = adamw.clip_by_global_norm(grads, tcfg.grad_clip)
        lr = schedules.lr_at(state["step"], tcfg)
        new_params, new_opt = adamw.update(grads, state["opt"],
                                           state["trainable"], lr, tcfg)
        bad = (~jnp.isfinite(loss)) | (~jnp.isfinite(gnorm)) \
            | (loss > tcfg.anomaly_threshold)
        keep_old = lambda new, old: jax.tree.map(
            lambda n, o: jnp.where(bad, o, n), new, old)
        state_out = {
            "step": state["step"] + 1,
            "trainable": keep_old(new_params, state["trainable"]),
            "opt": keep_old(new_opt, state["opt"]),
            "loss_ema": jnp.where(
                state["step"] == 0, loss,
                0.99 * state["loss_ema"] + 0.01 * jnp.where(bad, state["loss_ema"], loss)),
            "anomalies": state["anomalies"] + bad.astype(jnp.int32),
        }
        if compress:
            state_out["ef_residual"] = keep_old(new_residual,
                                                state["ef_residual"])
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   "skipped": bad.astype(jnp.int32)}
        return state_out, metrics

    return train_step


# ---------------------------------------------------------------------------
# Mesh placement (the dist/sharding.py rules)
# ---------------------------------------------------------------------------

def shard_train_state(model: Model, state: Dict, frozen: Dict, mesh,
                      fsdp: bool = None):
    """Place (state, frozen) on `mesh` per the sharding rules.
    Returns (state, frozen, state_sharding, frozen_sharding)."""
    from repro.dist import sharding as shd
    if fsdp is None:
        fsdp = shd.fsdp_default(model.cfg, mesh)
    st_sh = shd.named(state,
                      shd.state_specs(state, mesh, model.cfg, fsdp), mesh)
    fr_sh = shd.named(frozen,
                      shd.state_specs(frozen, mesh, model.cfg, fsdp), mesh)
    return (jax.device_put(state, st_sh), jax.device_put(frozen, fr_sh),
            st_sh, fr_sh)


def make_sharded_train_step(model: Model, tcfg: TrainConfig, mesh,
                            state: Dict, frozen: Dict, batch_example: Dict,
                            fsdp: bool = None, shardings=None):
    """jit the train step with explicit mesh shardings and donated state.
    `batch_example` may be real arrays or ShapeDtypeStructs; its leading dim
    is the global batch. `shardings`: the (state_sharding, frozen_sharding)
    pair from shard_train_state — pass it so placement and jit in_shardings
    share one source of truth (recomputed from `fsdp` only when
    absent). Returns (jitted_step, batch_sharding) — feed batches through
    `jax.device_put(batch, batch_sharding)` (train/loop.py does this when
    given `batch_sharding`)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import ShapeConfig
    from repro.dist import sharding as shd
    if shardings is not None:
        st_sh, fr_sh = shardings
    else:
        if fsdp is None:
            fsdp = shd.fsdp_default(model.cfg, mesh)
        st_sh = shd.named(state,
                          shd.state_specs(state, mesh, model.cfg, fsdp),
                          mesh)
        fr_sh = shd.named(frozen,
                          shd.state_specs(frozen, mesh, model.cfg, fsdp),
                          mesh)
    ref = batch_example.get("tokens", batch_example.get("embeds"))
    shape = ShapeConfig("runtime", int(ref.shape[1]), int(ref.shape[0]),
                        "train")
    b_sh = shd.named(batch_example,
                     shd.batch_specs(batch_example, mesh, shape), mesh)
    step = make_train_step(model, tcfg)

    def step_on_mesh(state, frozen, batch):
        # the mesh is visible while tracing, so Pallas kernels (which XLA
        # cannot partition) run per shard (kernels/ops.py)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step(state, frozen, batch)

    # the state leaves as it came in (a donated buffer keeps its placement)
    jitted = jax.jit(step_on_mesh, in_shardings=(st_sh, fr_sh, b_sh),
                     out_shardings=(st_sh, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
    return jitted, b_sh
