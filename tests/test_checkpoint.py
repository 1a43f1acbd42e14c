"""Checkpoint/fault-tolerance tests: roundtrip, atomicity under crash, keep-k,
async manager, resume, preemption, and elastic re-shard across device counts
(subprocess with a different XLA host-device count)."""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.checkpoint import manager as ckpt
from repro.configs.base import PEFTConfig, TrainConfig
from repro.data import SyntheticLM
from repro.models import build
from repro.train import loop, step as ts


def _tree():
    return {
        "a": jnp.arange(12.0).reshape(3, 4),
        "nested": {"b": jnp.ones((2, 2), jnp.bfloat16),
                   "c": jnp.int32(7)},
        "tup": (jnp.zeros(3), {"d": jnp.float32(1.5)}),
    }


class TestRoundtrip:
    def test_save_restore_identity(self, tmp_path):
        t = _tree()
        ckpt.save_sync(str(tmp_path), 5, t)
        out, step = ckpt.restore(str(tmp_path))
        assert step == 5
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # structure preserved (incl tuple)
        assert isinstance(out["tup"], tuple)

    def test_bfloat16_roundtrip(self, tmp_path):
        t = {"w": jnp.array([1.5, 2.5], jnp.bfloat16)}
        ckpt.save_sync(str(tmp_path), 1, t)
        out, _ = ckpt.restore(str(tmp_path))
        assert out["w"].dtype == jnp.bfloat16

    def test_latest_selected(self, tmp_path):
        for s in (1, 3, 2):
            ckpt.save_sync(str(tmp_path), s, {"x": jnp.float32(s)})
        out, step = ckpt.restore(str(tmp_path))
        assert step == 3 and float(out["x"]) == 3.0

    def test_atomicity_no_partial_checkpoints(self, tmp_path):
        """A tmp dir left behind by a crash must be invisible to restore."""
        ckpt.save_sync(str(tmp_path), 1, {"x": jnp.float32(1)})
        fake = tmp_path / "step_00000009.tmp-crashed"
        fake.mkdir()
        (fake / "x.npy").write_bytes(b"garbage")
        assert ckpt.available_steps(str(tmp_path)) == [1]


class TestManager:
    def test_async_keep_k(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=2)
        for s in range(5):
            mgr.save(s, {"x": jnp.float32(s)})
        mgr.wait()
        mgr.close()
        assert ckpt.available_steps(str(tmp_path)) == [3, 4]

    def test_error_surfaces(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path / "sub"), keep=1)
        mgr.save(0, {"x": jnp.float32(0)})
        mgr.close()  # should not raise
        assert ckpt.available_steps(str(tmp_path / "sub")) == [0]


class TestLoopFaultTolerance:
    def _setup(self):
        cfg = C.reduced(C.get("yi-6b")).replace(vocab=32)
        model = build(cfg, PEFTConfig(n=8, alpha=5.0))
        tcfg = TrainConfig(total_steps=12, warmup_steps=2)
        state, frozen = ts.init_state(model, tcfg, jax.random.PRNGKey(0))
        step_fn = jax.jit(ts.make_train_step(model, tcfg))
        data = SyntheticLM(vocab=32, batch=2, seq=8)
        return step_fn, state, frozen, data, tcfg

    def test_resume_from_checkpoint(self, tmp_path):
        step_fn, state, frozen, data, tcfg = self._setup()
        state1, rep1 = loop.run(step_fn, state, frozen, data, tcfg,
                                ckpt_dir=str(tmp_path), ckpt_every=5,
                                log_every=0, log_fn=lambda s: None)
        assert rep1.steps_run == 12
        # fresh state resumes from step 10 and runs only 2 steps
        state0, _ = ts.init_state(
            build(C.reduced(C.get("yi-6b")).replace(vocab=32),
                  PEFTConfig(n=8, alpha=5.0)), tcfg, jax.random.PRNGKey(0))
        state2, rep2 = loop.run(step_fn, state0, frozen, data, tcfg,
                                ckpt_dir=str(tmp_path), ckpt_every=5,
                                log_every=0, log_fn=lambda s: None)
        # the loop saves a final checkpoint at completion -> resume is a no-op
        assert rep2.resumed_from == 12
        assert rep2.steps_run == 0
        # drop the final checkpoint -> resume from the periodic one at 10
        import shutil
        shutil.rmtree(tmp_path / "step_00000012")
        state3, rep3 = loop.run(step_fn, state0, frozen, data, tcfg,
                                ckpt_dir=str(tmp_path), ckpt_every=5,
                                log_every=0, log_fn=lambda s: None)
        assert rep3.resumed_from == 10
        assert rep3.steps_run == 2

    def test_data_determinism_across_restarts(self):
        data = SyntheticLM(vocab=32, batch=4, seq=8, seed=11)
        b1 = data.batch_at(7)
        data2 = SyntheticLM(vocab=32, batch=4, seq=8, seed=11)
        b2 = data2.batch_at(7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        s0 = data.batch_at(3, shard=0, num_shards=2)
        s1 = data.batch_at(3, shard=1, num_shards=2)
        full = data.batch_at(3)
        np.testing.assert_array_equal(
            np.concatenate([s0["tokens"], s1["tokens"]]), full["tokens"])


ELASTIC_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(ndev)d"
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import manager as ckpt
from repro.launch.mesh import make_mesh
mesh = make_mesh((%(ndev)d,), ("model",))
w = jnp.arange(64.0).reshape(8, 8)
sharded = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
if "%(mode)s" == "save":
    ckpt.save_sync(sys.argv[1], 3, {"w": sharded})
else:
    sh = {"w": NamedSharding(mesh, P(None, "model"))}
    out, step = ckpt.restore(sys.argv[1], shardings=sh)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(64.0).reshape(8,8))
    assert len(out["w"].sharding.device_set) == %(ndev)d
print("OK")
"""


@pytest.mark.parametrize("save_dev,load_dev", [(4, 2), (2, 8)])
def test_elastic_reshard_across_device_counts(tmp_path, save_dev, load_dev):
    """Save sharded on N devices, restore sharded on M != N (elastic)."""
    env = dict(os.environ, PYTHONPATH="src")
    for mode, ndev in (("save", save_dev), ("load", load_dev)):
        script = ELASTIC_SCRIPT % {"ndev": ndev, "mode": mode}
        r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                           capture_output=True, text=True, env=env,
                           cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
        assert r.returncode == 0, r.stderr[-2000:]
        assert "OK" in r.stdout
