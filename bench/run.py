#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json. Set-up (weights drawn
from the seed on the device, compilation, warm-up of every shape the cell
uses) is timed as `setup_s`; then the cell's traffic runs for `--seconds`.
With `--trace 0` the last line of standard output is a JSON object with the
cell's end-to-end metrics; with `--trace 1` the window runs under JAX's
profiler and the object carries the per-layer metrics instead. After the
window the outputs are compared with a plain float32 reference (`correct`);
the compared numbers and their limits are the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload, ROOT)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # the persistent compilation cache lives at a fixed path in this checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"bench: the program under test is missing ({e})",
              file=sys.stderr)
        return 2
    setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = harness.require_tpu(cell.chips)
        harness.peaks_for(devices[0].device_kind)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    import importlib
    driver = importlib.import_module(f"bench.drivers.{cell.traffic['kind']}")
    res = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START,
                     devices=devices)
    harness.finish(cell, res, devices, trace=bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
