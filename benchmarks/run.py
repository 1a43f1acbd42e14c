"""Benchmark driver — one module per paper table/figure (+ systems benches).
Prints ``name,us_per_call,derived`` CSV. `python -m benchmarks.run [--only X]`.

Serving rows (`serve_*`) are additionally written to ``BENCH_serve.json``
at the repo root — tok/s, TTFT quantiles, speculative acceptance — so the
serving perf trajectory is machine-diffable across PRs instead of living
only in stdout. Analyzer rows (`analysis_*`: pass latency + finding
counts) land in ``BENCH_analysis.json`` the same way.
"""
import argparse
import importlib
import json
import pathlib
import sys
import time
import traceback

from benchmarks.common import ROWS

MODULES = [
    "benchmarks.bench_table1_params",
    "benchmarks.bench_table2_glue_proxy",
    "benchmarks.bench_table3_e2e_proxy",
    "benchmarks.bench_table4_instruct_proxy",
    "benchmarks.bench_table5_vision_proxy",
    "benchmarks.bench_table6_basis",
    "benchmarks.bench_fig4_scalability",
    "benchmarks.bench_fig5_freq_bias",
    "benchmarks.bench_fig6_curve",
    "benchmarks.bench_kernels",
    "benchmarks.bench_grad_comm",
    "benchmarks.bench_adapter_bank",
    "benchmarks.bench_serve_scheduler",
    "benchmarks.bench_serve_paging",
    "benchmarks.bench_serve_spec",
    "benchmarks.bench_serve_gateway",
    "benchmarks.bench_serve_tiering",
    "benchmarks.bench_analysis",
]

SERVE_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_serve.json"
ANALYSIS_JSON = SERVE_JSON.with_name("BENCH_analysis.json")


def parse_row(row: str) -> tuple:
    """`name,us_per_call,k=v;k=v` -> (name, {us_per_call, k: v, ...}) with
    numeric values parsed (the emit() contract keeps values float-able;
    anything else stays a string rather than failing the dump)."""
    name, us, derived = row.split(",", 2)
    rec = {"us_per_call": float(us)}
    for kv in derived.split(";"):
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            rec[k] = float(v)
        except ValueError:
            rec[k] = v
    return name, rec


def dump_prefix_json(rows, prefix, path) -> dict:
    """Merge every `<prefix>*` row into the JSON object keyed by row name:
    re-run rows replace their previous values, rows a partial run (e.g.
    `--only serve_gateway`) did not produce keep theirs, and empty runs
    leave the file alone."""
    picked = dict(parse_row(r) for r in rows if r.startswith(prefix))
    if picked:
        merged = {}
        if path.exists():
            try:
                merged = json.loads(path.read_text())
            except json.JSONDecodeError:
                merged = {}                    # corrupt file: rebuild
        merged.update(picked)
        path.write_text(json.dumps(merged, indent=2, sort_keys=True) + "\n")
    return picked


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.perf_counter()
        try:
            importlib.import_module(mod_name).main()
            print(f"# {mod_name} done in {time.perf_counter()-t0:.1f}s",
                  flush=True)
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
    if dump_prefix_json(ROWS, "serve", SERVE_JSON):
        print(f"# serving rows -> {SERVE_JSON}", flush=True)
    if dump_prefix_json(ROWS, "analysis", ANALYSIS_JSON):
        print(f"# analysis rows -> {ANALYSIS_JSON}", flush=True)
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
