"""The control — the plain reference in float8, the precision below the
configuration's bfloat16, put in the program's place — must come out not
correct under the cell's comparison, here at a size the CPU holds (the
chip readings at the cells' own sizes are in PERF.md)."""
from __future__ import annotations

import jax

from bench import calibrate
from bench.tests.tiny import tiny_cell

SEED = 2**33 + 17


def test_training_control_fails_the_comparison():
    cell = tiny_cell("qwen3-4b.train-sft512")
    out = calibrate.train_readings(cell, SEED, jax.devices()[:1])
    for reading in ("control", "half_batch"):
        failed = [k for k, v in out[reading].items() if v > cell.limits[k]]
        assert failed, (reading, out[reading], cell.limits)


def test_serving_control_fails_the_comparison():
    cell = tiny_cell("qwen3-4b.serve-64tenants")
    out = calibrate.serve_readings(cell, SEED, 2.0, jax.devices()[:1])
    assert out["program"]["logit_gap"] <= cell.limits["logit_gap"], out
    assert out["control"]["logit_gap"] > cell.limits["logit_gap"], out
