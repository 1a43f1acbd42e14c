"""How the harness finds a cell's files, and what it does without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _copy_benchmark(dst: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def test_every_declared_cell_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert (ROOT / "bench" / "drivers"
                / f"{cell.traffic['kind']}.py").exists()
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds a cell as new files plus new entries: nothing
    already in bench/ is edited."""
    _copy_benchmark(tmp_path)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/qwen3-4b.json").read_text())
    conf["name"] = "new-model"
    (tmp_path / "bench/configs/new-model.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/train-sft512.json").read_text())
    mix["seq_len"] = 2048
    (tmp_path / "bench/traffic/train-sft2048.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/limits/new-model.train-sft2048.json").write_text(
        json.dumps({"limits": {"loss": 1, "grad1": 1, "change": 1}}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return None if ctx.get('x') is None "
        "else 2 * ctx['x']\n")
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-model.train-sft2048",
                               "config": "new-model",
                               "traffic": "train-sft2048", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == "train_tokens_per_s":
            m["workloads"].append("new-model.train-sft2048")
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels",
                               "moves": "train_tokens_per_s",
                               "workloads": ["new-model.train-sft2048"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("new-model.train-sft2048", tmp_path)
    assert cell.config["name"] == "new-model"
    assert cell.traffic["seq_len"] == 2048
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert "train_tokens_per_s" in [m["name"] for m in cell.end_to_end]
    assert harness.read_per_layer(cell, {"x": 21.0})["new_metric"] == \
        {"value": 42.0, "unit": "%"}
    assert "new_metric" not in [m["name"] for m in harness.load_cell(
        "qwen3-4b.train-sft512", tmp_path).per_layer]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v999")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _run(cwd: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-4b.train-sft512", "--seed", str(2**35 + 1), "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_without_the_program_exits_nonzero(tmp_path):
    _copy_benchmark(tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
