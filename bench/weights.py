"""Weights and adapters drawn from the seed by the benchmark, in the
program's parameter layout and placements, in one jitted call on the
devices. The plain reference reads these same arrays; nothing here comes
from the program's own initializers.

Leaf rules (by the leaf's name in the program's tree):
  - matrices and embeddings: normal(0, 0.02) in the parameter dtype;
  - norm weights (`*norm`): 1 + normal(0, 0.1);
  - FourierFT coefficients `c`: normal(0, 1) in float32;
  - FourierFT `entries`: n distinct spectral (u, v) per adapted shape, drawn
    on the host from the seed;
  - optimizer moments and counters: zero.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import numpy as np

from bench import harness


def draw_entries(seed: int, site: str, d1: int, d2: int, n: int) -> np.ndarray:
    """(2, n) int32 distinct (u, v) on the d1 x d2 grid."""
    rng = harness.np_rng(seed, zlib.crc32(site.encode()))
    flat = rng.choice(d1 * d2, size=n, replace=False)
    return np.stack(np.divmod(flat, d2)).astype(np.int32)


def _paths(tree, prefix=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _paths(v, prefix + (str(k),))
    else:
        yield prefix, tree


def _set(tree: Dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _leaf(key, path, shape_dtype, entries):
    import jax
    import jax.numpy as jnp
    name = path[-1]
    shape, dtype = shape_dtype.shape, shape_dtype.dtype
    if name == "entries":
        return jnp.asarray(entries[path[-2]])
    if path[0] in ("opt", "step", "loss_ema", "anomalies"):
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode())
                           & 0x7FFFFFFF)
    if name == "c":
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    if name.endswith("norm"):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def train_state(model, tcfg, mesh, seed: int) -> Tuple:
    """(state, frozen, state_sharding, frozen_sharding, entries) for the
    program's train step, drawn from `seed` into the plan's placements."""
    import jax
    from repro.dist import plan as plan_mod
    from repro.dist import sharding as shd
    from repro.train import step as train_step

    shapes = jax.eval_shape(
        lambda k: train_step.init_state(model, tcfg, k), jax.random.PRNGKey(0))
    src = plan_mod.RulesSource()
    fsdp = shd.fsdp_default(model.cfg, mesh)
    st_sh = shd.named(None, src.state_specs(shapes[0], mesh, model.cfg, fsdp),
                      mesh)
    fr_sh = shd.named(None, src.state_specs(shapes[1], mesh, model.cfg, fsdp),
                      mesh)
    entries = {}
    for site in model.sites:
        if site.name.split("/")[-1] in model.peft.target_modules:
            entries[site.name] = draw_entries(seed, site.name, site.d_in,
                                              site.d_out, model.peft.n)

    def make(key, entries):
        state, frozen = {}, {}
        for path, sd in _paths(shapes[0]):
            _set(state, path, _leaf(key, path, sd, entries))
        for path, sd in _paths(shapes[1]):
            _set(frozen, ("frozen",) + path,
                 _leaf(key, ("frozen",) + path, sd, entries))
        return state, frozen["frozen"]

    state, frozen = jax.jit(make, out_shardings=(st_sh, fr_sh))(
        harness.prng_key(seed, "weights"), entries)
    return state, frozen, st_sh, fr_sh, entries


def serve_params(model, prof, mesh, plan, seed: int, n_tenants: int,
                 delta_rms: float) -> Tuple:
    """(params, entries, coefs) for serving: the base model's parameters
    in the plan's placements; per adapted site the spectral entries (2, n)
    and every tenant's coefficients (tenants, L, n), scaled so that a
    tenant's ΔW entries have RMS `delta_rms`."""
    import jax
    import jax.numpy as jnp
    from repro.dist import sharding as shd

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sh = shd.named(None, plan.state_specs(shapes, mesh, model.cfg, False),
                   mesh)
    sites = [s for s in model.sites
             if s.name.split("/")[-1] in prof.target_modules]
    entries = {s.name: draw_entries(seed, s.name, s.d_in, s.d_out, prof.n)
               for s in sites}

    def make(key):
        params = {}
        for path, sd in _paths(shapes):
            _set(params, path, _leaf(key, path, sd, entries))
        params.setdefault("peft", {})
        coefs = {}
        for s in sites:
            # RMS of one ΔW entry per unit coefficient: α/(d1·d2)·sqrt(n/2)
            unit = prof.alpha / (s.d_in * s.d_out) * (prof.n / 2) ** 0.5
            k = jax.random.fold_in(key, zlib.crc32(s.name.encode())
                                   & 0x7FFFFFFF)
            coefs[s.name] = (delta_rms / unit) * jax.random.normal(
                k, (n_tenants, s.stack, prof.n), jnp.float32)
        return params, coefs

    params, coefs = jax.jit(make, out_shardings=(sh, None))(
        harness.prng_key(seed, "serve"))
    return params, entries, coefs
