"""Sharding-rule tests (DESIGN.md §Sharding): `dist/plan.RulesSource` is the
rule table byte for byte, `plan.resolve` knows only the rules, the rules'
placement of yi-9b on its 1x4 mesh is pinned, the analyzer's per-kind
collective buckets, and a compiled 8-fake-device smoke of the train and
decode steps users run, placed by the rules."""
import dataclasses
import os
import subprocess
import sys
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.configs.base import PEFTConfig, ShapeConfig
from repro.dist import hlo
from repro.dist import plan as plan_mod
from repro.dist import sharding as shd
from repro.models import build, registry


@dataclasses.dataclass(frozen=True)
class AbstractMesh:
    """What the rules read of a mesh: axis names and the device grid's
    shape. Nothing is allocated and no device is needed."""
    axes: Tuple[Tuple[str, int], ...]

    @property
    def axis_names(self):
        return tuple(a for a, _ in self.axes)

    @property
    def devices(self):
        return np.empty(tuple(n for _, n in self.axes), dtype=np.int8)


MESHES = (AbstractMesh((("data", 4), ("model", 2))),
          AbstractMesh((("pod", 2), ("data", 4), ("model", 2))))


def _flat_specs(tree, path=()):
    """(path, spec-as-tuple) pairs; PartitionSpec is a leaf, not a tuple
    container."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat_specs(tree[k], path + (str(k),))
    elif isinstance(tree, (list, tuple)) and not isinstance(tree, P):
        for i, v in enumerate(tree):
            yield from _flat_specs(v, path + (str(i),))
    else:
        yield "/".join(path), tuple(tree)


def _tiny(arch="yi-6b", method="fourierft"):
    cfg = C.reduced(C.get(arch)).replace(vocab=64)
    return build(cfg, PEFTConfig(method=method, n=16))


def _sweep():
    """Every arch x fourierft plus every audited method on the first arch —
    the same coverage surface the sharding audit walks."""
    yield from registry.analysis_models()
    from repro.analysis.sharding_audit import DEFAULT_METHODS
    first = C.ARCH_IDS[0]
    yield from registry.analysis_models(methods=DEFAULT_METHODS[1:],
                                        archs=(first,))


class TestRulesByteIdentity:
    @pytest.mark.parametrize("mesh", MESHES, ids=lambda m: "x".join(
        map(str, m.devices.shape)))
    def test_state_specs_every_arch_method(self, mesh):
        """RulesSource == the rule table's module functions."""
        rules = plan_mod.RulesSource()
        for arch, method, model in _sweep():
            tree = model.init_shapes()
            for fsdp in (False, True):
                want = shd.state_specs(tree, mesh, model.cfg, fsdp=fsdp)
                got = rules.state_specs(tree, mesh, model.cfg, fsdp=fsdp)
                assert list(_flat_specs(got)) == list(_flat_specs(want)), \
                    f"{arch}[{method}] fsdp={fsdp}"

    def test_cache_and_batch_specs_match(self):
        mesh = MESHES[0]
        model = _tiny()
        shape = ShapeConfig("decode", 32, 8, "decode")
        cache = jax.eval_shape(lambda: model.init_cache(8, 32))
        batch = {"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32)}
        rules = plan_mod.RulesSource()
        assert (list(_flat_specs(rules.cache_specs(cache, mesh, model.cfg,
                                                   shape)))
                == list(_flat_specs(shd.cache_specs(cache, mesh, model.cfg,
                                                    shape))))
        assert (list(_flat_specs(rules.batch_specs(batch, mesh, shape)))
                == list(_flat_specs(shd.batch_specs(batch, mesh, shape))))

    def test_leaf_rules_pin_known_placements(self):
        """The extracted leaf functions keep the legacy decisions."""
        mesh = MESHES[0]
        b = shd.batch_axes(mesh, 8)
        # compare against a PartitionSpec built from the same entries: jax
        # normalises a one-axis tuple entry ('data',) to 'data'
        want = tuple(P(None, b))
        assert tuple(shd.cache_leaf_spec("layers/k", (2, 4, 32, 4, 8),
                                         mesh, b))[:2] == want
        assert tuple(shd.batch_leaf_spec("tokens", (8, 32), b))[0] == want[1]
        assert shd.batch_rule_kind("tokens", (8, 32)) == "batch"
        assert shd.cache_rule_kind("layers/k", (2, 4, 32, 4, 8)) == "kv"
        assert shd.cache_rule_kind("layers/pk", (2, 4, 16, 8, 8, 8)) is None


@pytest.mark.parametrize("arg", ["search", "plan.json"])
def test_resolve_only_rules(arg):
    """The rules are the only placement: anything else is refused by name."""
    for ok in (None, "", "rules"):
        assert isinstance(plan_mod.resolve(ok), plan_mod.RulesSource)
    with pytest.raises(ValueError, match="rules"):
        plan_mod.resolve(arg)


# What the rules give the yi-9b cell on its 1x4 (data 1, model 4) mesh:
# column-parallel projections on `model` in their last dim, row-parallel
# ones and the embedding in their second-to-last, the FourierFT leaves and
# norms replicated.
YI9B_X4 = {
    "wq": (None, None, "model"),
    "wk": (None, None, "model"),
    "wv": (None, None, "model"),
    "wi": (None, None, "model"),
    "wg": (None, None, "model"),
    "wo": (None, "model", None),
    "wo_mlp": (None, "model", None),
    "embed": ("model", None),
    "lm_head": (None, "model"),
    "attn_norm": (None, None),
    "c": (None, None),
    "entries": (None, None),
}


@pytest.fixture(scope="module")
def yi9b_x4_specs():
    """{path: spec} for yi-9b at its published widths with FourierFT n=1000
    on wq/wv, placed by the rules on an abstract 1x4 mesh (shapes only)."""
    model = build(C.get("yi-9b"),
                  PEFTConfig(method="fourierft", n=1000, alpha=300.0,
                             target_modules=("wq", "wv")))
    mesh = AbstractMesh((("data", 1), ("model", 4)))
    tree = model.init_shapes()
    specs = plan_mod.RulesSource().state_specs(
        tree, mesh, model.cfg, shd.fsdp_default(model.cfg, mesh))
    return dict(_flat_specs(specs))


@pytest.mark.parametrize("leaf", sorted(YI9B_X4))
def test_yi9b_four_chip_placement(yi9b_x4_specs, leaf):
    hits = {p: s for p, s in yi9b_x4_specs.items()
            if p.split("/")[-1] == leaf}
    assert hits, f"no {leaf!r} leaf in the yi-9b tree"
    for path, spec in hits.items():
        assert spec == YI9B_X4[leaf], (path, spec)
    on_data = [p for p, s in yi9b_x4_specs.items()
               if any(e == "data" or (isinstance(e, tuple) and "data" in e)
                      for e in s)]
    assert not on_data, on_data


class TestHloCollectiveBuckets:
    A2A = """HloModule m
ENTRY %main (p: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  ROOT %a2a = f32[64,64]{1,0} all-to-all(%p), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
}
"""
    PERMUTE_ASYNC = """HloModule m
ENTRY %main (p: f32[32,32]) -> f32[32,32] {
  %p = f32[32,32]{1,0} parameter(0)
  %cps = f32[32,32]{1,0} collective-permute-start(%p), source_target_pairs={{0,1},{1,0}}
  ROOT %cpd = f32[32,32]{1,0} collective-permute-done(%cps)
}
"""

    def test_all_to_all_own_bucket(self):
        s = hlo.analyze_module(self.A2A)
        assert s.bytes_by_kind == {"all-to-all": 64 * 64 * 4}
        assert s.count_by_kind["all-to-all"] == 1
        assert s.group_by_kind["all-to-all"] == 4

    def test_collective_permute_async_counted_once(self):
        s = hlo.analyze_module(self.PERMUTE_ASYNC)
        assert s.bytes_by_kind == {"collective-permute": 32 * 32 * 4}
        assert s.count_by_kind["collective-permute"] == 1
        assert s.group_by_kind["collective-permute"] == 2

    def test_replica_group_size_forms(self):
        assert hlo.replica_group_size("replica_groups={{0,1},{2,3}}") == 2
        assert hlo.replica_group_size("replica_groups=[2,4]<=[8]") == 4
        assert hlo.replica_group_size(
            "source_target_pairs={{0,1},{1,2},{2,0}}") == 2
        assert hlo.replica_group_size("channel_id=3") is None


STEP_SMOKE = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
import repro.configs as C
from repro.configs.base import PEFTConfig, ShapeConfig, TrainConfig
from repro.data import SyntheticLM
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.serve import Engine
from repro.train import step as ts

arch, mode = sys.argv[1], sys.argv[2]
cfg = C.reduced(C.get(arch), layers=2, width=64, vocab=256)
model = build(cfg, PEFTConfig(method="fourierft", n=16))
mesh = make_mesh((4, 2), ("data", "model"))


def on_model(sh):
    return "model" in jax.tree.leaves(tuple(sh.spec))


def assert_placed(x, want):
    assert x.sharding.is_equivalent_to(want, x.ndim), (x.sharding, want)


if mode == "train":
    tcfg = TrainConfig(total_steps=2, warmup_steps=1)
    state, frozen = ts.init_state(model, tcfg, jax.random.PRNGKey(0),
                                  mesh=mesh)
    state, frozen, st_sh, fr_sh = ts.shard_train_state(model, state, frozen,
                                                       mesh)
    data = SyntheticLM(vocab=cfg.vocab, batch=8, seq=16, seed=0)
    batch = data.batch_at(0)
    step, b_sh = ts.make_sharded_train_step(model, tcfg, mesh, state, frozen,
                                            batch, shardings=(st_sh, fr_sh))
    batch = jax.device_put(batch, b_sh)
    step.lower(state, frozen, batch).compile()
    assert any(on_model(s) for s in jax.tree.leaves(fr_sh)), "no TP leaf"
    state, m = step(state, frozen, batch)
    assert np.isfinite(float(m["loss"])), m
else:
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, batch_slots=8, max_len=32, mesh=mesh)
    p_sh = shd.named(engine.params, shd.state_specs(engine.params, mesh, cfg),
                     mesh)
    jax.tree.map(lambda x, w: assert_placed(x, w), engine.params, p_sh)
    assert any(on_model(s) for s in jax.tree.leaves(p_sh)), "no TP leaf"
    cache = engine._fresh_cache()
    want = shd.named(cache, shd.cache_specs(
        cache, mesh, cfg, ShapeConfig("serve", 32, 8, "decode")), mesh)
    jax.tree.map(lambda x, w: assert_placed(x, w), cache, want)
    batch = {"tokens": engine.commit_tokens(np.zeros((8, 1), np.int32))}
    decode = engine._decode.lower(engine.params, cache, batch).compile()
    nt, cache = decode(engine.params, cache, batch)
    assert nt.shape[0] == 8 and int(cache["pos"]) == 1, nt.shape
print("STEP_SMOKE_OK")
"""

FAMILIES = {"dense": "yi-6b", "moe": "olmoe-1b-7b", "hybrid": "zamba2-7b"}


@pytest.mark.parametrize("mode", ["train", "decode"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sharded_step_compiles(family, mode):
    """8 fake devices on a (4, 2) data x model mesh: the rules place the
    train state (`train.step.make_sharded_train_step`) or the serving
    params and cache (`serve.Engine`), and one step compiles and runs."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", STEP_SMOKE, FAMILIES[family], mode],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "STEP_SMOKE_OK" in r.stdout
