"""The four-chip training cell (`yi-9b.train-sft512.x4`) at a size the CPU
runs, on four virtual CPU devices: the train driver on a (data 1, model 4)
mesh, with the FourierFT ΔW kernels in interpret mode so that they run in
the layer-split `shard_map` of `kernels/ops.py` and their result is
exchanged for the merge, as on the chips.

The cell is cut here, in the test, to a tiny yi shape that keeps what the
mesh splits: GQA with as many KV heads as chips (one a chip), as many layers
as chips (one layer of the ΔW stack a chip), and column and row splits of
every matrix over `model`. `tiny.py` stays a one-chip cut.

One subprocess (the device count is fixed when JAX starts) runs the cell
sound on the four-chip mesh, the same cell on a one-device mesh, and the
four-chip cell with the exchange-left-out fault planted, and prints what
each read."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.tiny import TINY_LIMITS

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import copy, json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import jax.numpy as jnp

from bench import harness
from bench.drivers import train
from bench.tests.tiny import TINY_LIMITS
from repro.kernels import ops

SEED = 2**33 + 29
YI_TINY = {"hidden_size": 64, "num_attention_heads": 8,
           "num_key_value_heads": 4, "intermediate_size": 128,
           "num_hidden_layers": 4, "vocab_size": 4096}


def tiny_yi(model):
    cell = copy.deepcopy(harness.load_cell("yi-9b.train-sft512.x4"))
    assert cell.chips == 4 and cell.config["train"]["mesh"]["model"] == 4
    cell.limits = dict(TINY_LIMITS["train"])
    cell.config["config"].update(YI_TINY)
    cell.config["peft"].update(n=16, kernel_backend="interpret")
    cell.config["train"].update(batch=4, mesh={"data": 1, "model": model})
    cell.traffic["seq_len"] = 16
    return cell


def exchange_left_out(harness_fn):
    # Each chip builds the ΔW of its own layers (the layer-split shard_map)
    # and holds a column block of every W. Without the exchange between
    # the two layouts, the merge W + ΔW of layer l gets ΔW only in the
    # columns of the chip that built layer l, and zero in the others.
    def faulty(c, entries, d1, d2, alpha, *, interpret=False):
        dw = harness_fn(c, entries, d1, d2, alpha, interpret=interpret)
        mesh = jax.sharding.get_abstract_mesh()
        n = 1 if mesh.empty else mesh.shape["model"]
        L = dw.shape[0]
        owner = jnp.arange(L) // (L // n)
        column = jnp.arange(d2) // (d2 // n)
        return dw * (owner[:, None, None] == column[None, None, :])
    return faulty


def run(model):
    first = []

    def record(step_fn, m, tcfg):
        def step(state, frozen, batch):
            state, metrics = step_fn(state, frozen, batch)
            if len(first) < 3:
                first.append(metrics["loss"])
            return state, metrics
        return step

    res = train.run(tiny_yi(model), seed=SEED, seconds=0.5, trace=False,
                    t_start=time.perf_counter(),
                    devices=jax.devices()[:model], wrap_step=record)
    return {"correct": res.correct, "attempted": res.attempted,
            "failed": res.failed,
            "checks": {c.name: [c.value, c.limit] for c in res.checks},
            "losses": [float(x) for x in first],
            "policy": res.notes[0]}


out = {"devices": len(jax.devices()), "sound": run(4), "one": run(1)}
ops.fourier_deltaw_harness = exchange_left_out(ops.fourier_deltaw_harness)
out["fault"] = run(4)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, p.stdout[-2000:]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["devices"] == 4
    return out


def test_sound_four_chip_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # the ΔW kernels ran (interpret mode), not the einsum fallback
    assert "deltaw -> interpret" in r["policy"], r["policy"]


def test_four_chip_losses_match_one_device(runs):
    four, one = runs["sound"]["losses"], runs["one"]["losses"]
    assert len(four) == len(one) == 3
    gap = max(abs(a - b) / abs(b) for a, b in zip(four, one))
    assert gap <= TINY_LIMITS["train"]["loss"], (four, one)
    assert runs["one"]["correct"], runs["one"]["checks"]


def test_exchange_left_out_is_not_correct(runs):
    r = runs["fault"]
    assert not r["correct"], r["checks"]
