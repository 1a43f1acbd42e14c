"""Sharding-plan tests (DESIGN.md §Sharding): PlanSource byte-identity with
the rule table, plan serialization round-trips, planner search never losing
to the rules under its own cost model, the analyzer's per-kind collective
buckets, and a compiled 8-fake-device smoke showing a searched plan beating
the rules on analyzer-measured collective bytes while staying fp32-equivalent
for train and serve."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.configs.base import PEFTConfig, ShapeConfig
from repro.dist import hlo
from repro.dist import plan as plan_mod
from repro.dist import planner
from repro.dist import sharding as shd
from repro.dist.cost_model import ClusterEnv, MeshSpec
from repro.models import build, registry

MESHES = (MeshSpec.from_string("4x2"), MeshSpec.from_string("2x4x2"))


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
        """RulesSource == the legacy module functions, and a plan table built
        FROM the rules specs reproduces them exactly after the
        encode -> JSON -> decode -> sanitize round trip."""
        rules = plan_mod.RulesSource()
        for arch, method, model in _sweep():
            tree = model.init_shapes()
            for fsdp in (False, True):
                want = shd.state_specs(tree, mesh, model.cfg, fsdp=fsdp)
                got = rules.state_specs(tree, mesh, model.cfg, fsdp=fsdp)
                assert list(_flat_specs(got)) == list(_flat_specs(want)), \
                    f"{arch}[{method}] fsdp={fsdp}"
                plan = plan_mod.ShardingPlan(meta={}, tables={})
                shapes = dict(planner._iter_leaves(tree))
                for path, spec in _flat_specs(want):
                    plan.put("state", path,
                             tuple(shapes[path].shape), spec)
                via_table = plan_mod.PlanTableSource(plan).state_specs(
                    tree, mesh, model.cfg, fsdp=fsdp)
                assert (list(_flat_specs(via_table))
                        == list(_flat_specs(want))), \
                    f"{arch}[{method}] fsdp={fsdp} plan round-trip"

    def test_cache_and_batch_specs_match(self):
        mesh = MESHES[0]
        model = _tiny()
        shape = ShapeConfig("decode", 32, 8, "decode")
        cache = model.cache_specs(shape)
        batch = model.input_specs(shape)
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


class TestPlanRoundTrip:
    def test_serialize_load_identical(self, tmp_path):
        model = _tiny()
        mesh = MESHES[0]
        shape = ShapeConfig("train", 32, 8, "train")
        plan = planner.plan_model(model, mesh, shape=shape, workload="train")
        p = tmp_path / "plan.json"
        plan.save(str(p))
        loaded = plan_mod.ShardingPlan.load(str(p))
        assert loaded.to_json() == plan.to_json()
        tree = model.init_shapes()
        a = plan_mod.PlanTableSource(plan).state_specs(tree, mesh, model.cfg)
        b = plan_mod.PlanTableSource(loaded).state_specs(tree, mesh,
                                                         model.cfg)
        assert list(_flat_specs(a)) == list(_flat_specs(b))

    def test_sanitize_degrades_across_meshes(self):
        # an axis the mesh lacks, or that doesn't divide, drops to replicate
        assert tuple(plan_mod.sanitize_spec(P("model", "data"), (7, 8),
                                            MESHES[0])) == (None, "data")
        assert tuple(plan_mod.sanitize_spec(P("pod"), (8,),
                                            MESHES[0])) == (None,)


class TestPlannerSearch:
    @pytest.mark.parametrize("workload,shape", [
        ("train", ShapeConfig("train", 64, 8, "train")),
        ("decode", ShapeConfig("decode", 64, 8, "decode")),
    ])
    def test_search_never_worse_than_rules(self, workload, shape):
        model = _tiny()
        for mesh in MESHES:
            plan = planner.plan_model(model, mesh, shape=shape,
                                      workload=workload)
            ranked = plan.meta["ranked"]
            rules_obj = next(r["objective_s"] for r in ranked
                             if r["strategy"] == "rules")
            assert ranked[0]["objective_s"] <= rules_obj * (1 + 1e-9)

    def test_score_source_prices_placements(self):
        model = _tiny()
        mesh = MESHES[0]
        shape = ShapeConfig("train", 64, 8, "train")
        cost = planner.score_source(model, mesh, shape,
                                    plan_mod.RulesSource(), workload="train")
        assert cost.total_s > 0 and cost.resident_bytes > 0

    def test_cost_model_collective_formulas(self):
        env = ClusterEnv(MESHES[0])
        nbytes = 1 << 20
        ar = env.all_reduce_cost(nbytes, ("data",))
        ag = env.all_gather_cost(nbytes, ("data",))
        assert ar > ag > 0                       # 2(n-1)/n vs (n-1)/n
        assert env.all_reduce_cost(nbytes, ()) == 0.0


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


PLAN_SMOKE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
import repro.configs as C
from repro.launch import dryrun_lib as dl
from repro.launch.mesh import make_mesh
from repro.configs.base import PEFTConfig, ShapeConfig, TrainConfig
from repro.models import build
from repro.train import step as ts

orig_get = C.get
dl.configs.get = lambda a: C.reduced(orig_get(a), layers=2, width=64, vocab=256)
shapes = {"train_4k": ShapeConfig("train_4k", 128, 8, "train"),
          "decode_32k": ShapeConfig("decode_32k", 256, 8, "decode")}
dl.configs.shape_for = lambda n: shapes[n]
mesh = make_mesh((4, 2), ("data", "model"))

# 1) searched plan beats the rules on ANALYZER-MEASURED collective bytes
for shape, strict in (("decode_32k", True), ("train_4k", True)):
    coll = {}
    for plan in ("rules", "search"):
        cell = dl.build_cell("yi-6b", shape, mesh, sharding_plan=plan)
        with mesh:
            compiled = dl.lower_cell(cell).compile()
        res = dl.analyze(cell, None, compiled, mesh, 0.0)
        coll[plan] = res["collective_bytes_per_device"]
        assert res["sharding_plan"]["source"] == (
            "rules" if plan == "rules" else "plan")
        assert "predicted" in res["sharding_plan"]
    assert coll["search"] <= coll["rules"], (shape, coll)
    if strict:
        assert coll["search"] < coll["rules"], (shape, coll)

# 2) fp32 train equivalence: same losses under rules and searched plans
cfg = C.reduced(orig_get("yi-6b"), layers=2, width=64, vocab=256).replace(
    param_dtype="float32", dtype="float32")
peft = PEFTConfig(method="fourierft", n=16, param_dtype="float32")
model = build(cfg, peft)
tcfg = TrainConfig(learning_rate=1e-2, total_steps=4, warmup_steps=1)
from repro.data import SyntheticLM
data = SyntheticLM(vocab=256, batch=8, seq=16, seed=0)
losses = {}
from repro.dist import plan as plan_mod
for kind in ("rules", "search"):
    src = plan_mod.resolve(kind, model=model, mesh=mesh,
                           shape=ShapeConfig("t", 16, 8, "train"),
                           workload="train")
    state, frozen = ts.init_state(model, tcfg, jax.random.PRNGKey(0))
    state, frozen, st_sh, fr_sh = ts.shard_train_state(
        model, state, frozen, mesh, plan=src)
    step_fn, b_sh = ts.make_sharded_train_step(
        model, tcfg, mesh, state, frozen, data.batch_at(0),
        shardings=(st_sh, fr_sh), plan=src)
    ls = []
    for i in range(3):
        state, m = step_fn(state, frozen,
                           jax.device_put(data.batch_at(i), b_sh))
        ls.append(float(m["loss"]))
    losses[kind] = ls
np.testing.assert_allclose(losses["rules"], losses["search"], rtol=1e-5)

# 3) serve equivalence: fp32 forward logits match under rules vs searched
# placement (token-level identity is too strict across placements: a
# random-init model's near-uniform logits flip argmax on reduction order)
from repro.dist import sharding as shd
params = model.init(jax.random.PRNGKey(0))
sshape = ShapeConfig("s", 16, 8, "prefill")
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16),
                                      0, 256)}
outs = {}
for kind in ("rules", "search"):
    src = plan_mod.resolve(kind, model=model, mesh=mesh, shape=sshape,
                           workload="prefill")
    p_sh = shd.named(params, src.state_specs(params, mesh, model.cfg), mesh)
    b_sh = shd.named(batch, src.batch_specs(batch, mesh, sshape), mesh)
    fwd = jax.jit(lambda p, b: model.forward(p, b)[0],
                  in_shardings=(p_sh, b_sh))
    with mesh:
        outs[kind] = np.asarray(fwd(jax.device_put(params, p_sh),
                                    jax.device_put(batch, b_sh)))
np.testing.assert_allclose(outs["rules"], outs["search"],
                           atol=1e-4, rtol=1e-4)
print("PLAN_SMOKE_OK")
"""


def test_searched_plan_compiled_smoke():
    """8-fake-device subprocess: searched plan reduces analyzer-measured
    collective bytes vs the rules and stays fp32-equivalent for train and
    serve (the PR-10 acceptance demonstration)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", PLAN_SMOKE],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PLAN_SMOKE_OK" in r.stdout
