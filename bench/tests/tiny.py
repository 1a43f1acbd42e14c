"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, for the
benchmark's own tests (same files, same drivers; only the sizes differ)."""
from __future__ import annotations

import copy

from bench import harness

TINY_MODEL = {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "intermediate_size": 128, "num_hidden_layers": 2,
              "vocab_size": 4096}

# Limits of the output comparison at this size, set as the cells' limits
# are (between the largest reading of sound runs and the smallest of the
# control or a fault), from CPU readings at this size: see PERF.md.
TINY_LIMITS = {"train": {"loss": 1.2e-4, "grad1": 0.04, "change": 0.012},
               "serve": {"logit_gap": 0.05}}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell = copy.deepcopy(cell)
    cell.limits = dict(TINY_LIMITS[cell.traffic["kind"]])
    cell.config["config"].update(TINY_MODEL)
    cell.config["peft"]["n"] = 16
    if "train" in cell.config:
        cell.config["train"].update(batch=4, mesh={"data": 1, "model": 1})
    if "serve" in cell.config:
        cell.config["serve"].update(slots=4, max_len=128, n_pages=None,
                                    eos_token_id=4095)
    if cell.traffic["kind"] == "train":
        cell.traffic["seq_len"] = 16
    if cell.traffic["kind"] == "serve":
        cell.traffic.update(
            rate=8.0, lead_in_s=1.0, tenants=4, warmup_tokens=2,
            checked_requests=8,
            prompt_tokens=dict(cell.traffic["prompt_tokens"], median=24,
                               min=8, max=64),
            output_tokens=dict(cell.traffic["output_tokens"], median=8,
                               min=4, max=16))
    return cell
