#!/usr/bin/env python3
"""Find the knee of a serving cell once, by a sweep on the chip: the
highest offered rate whose backlog does not grow over the window.

    python3 bench/sweep_knee.py --workload <serving cell> --seed <n> \
        --seconds <window> --rates 2,3,4,6

One process builds the cell's server once and offers each rate in turn
(the cell's mix with only `rate` changed, a lead-in before each window).
For each rate it prints one JSON line: the requests due in the window, the
scheduler's queue at the window's open and close, tokens/s, the TTFT tail
of the window's first and second halves, and the load generator's lag. A
rate whose queue at the close exceeds the queue at the open by more than a
few requests, or whose second-half TTFT keeps climbing, is past the knee.
The benchmark's runs never call this; the cell's traffic file records the
rate it chose.
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from bench import harness, traffic_gen
    from bench.drivers import serve

    cell = harness.load_cell(args.workload, ROOT)
    devices = harness.require_tpu(cell.chips)
    server, *_ = serve.build_server(cell, args.seed, devices)
    eos = cell.config["serve"].get("eos_token_id")

    async def sweep():
        await server.start("127.0.0.1", 0)
        try:
            for rate in (float(r) for r in args.rates.split(",")):
                c = copy.deepcopy(cell)
                c.traffic["rate"] = rate
                planned = traffic_gen.plan(c.traffic, args.seconds,
                                           args.seed, server.vocab, eos)
                await serve._warm(server, c, planned, eos)
                res, w0, w1, st, _ = await serve.session(
                    c, server, planned, args.seconds, False,
                    serve.workdir_for(c))
                em = serve.window_metrics(res, planned, w0, w1, eos,
                                          miss_at=w1 + serve.DRAIN_S)
                mid = (w0 + w1) / 2
                halves = []
                for lo, hi in ((w0, mid), (mid, w1)):
                    tt = [r["stamps"][0] - r["due"] for r in res
                          if lo <= r["due"] < hi and r["stamps"]]
                    halves.append(1e3 * serve.nearest_rank(tt, 0.9))
                print(json.dumps({"rate": rate, **em,
                                  "queued_open_close": st["queued"],
                                  "ttft_p90_ms_halves": halves,
                                  "occupancy": sum(st["occupancy"])
                                  / max(len(st["occupancy"]), 1)}),
                      flush=True)
                await asyncio.sleep(2.0)
        finally:
            await server.close()

    t0 = time.perf_counter()
    asyncio.run(sweep())
    print(f"sweep done in {time.perf_counter() - t0:.0f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
