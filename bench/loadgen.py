#!/usr/bin/env python3
"""Open-loop load generator for the serving gateway, run as its own process
(it never imports JAX, so the server's process keeps the chip and its own
interpreter lock).

    python3 bench/loadgen.py <schedule.json> <results.json>

The schedule holds the gateway's address, a start time `t0` on the
machine's monotonic clock (shared by every process), a `deadline` on the
same clock after which requests still streaming are cut off and marked
unfinished, and the requests, each with its due time in seconds after `t0`
and its /v1/completions payload.
Every request is sent at its due time whether or not earlier ones have
finished. Per request the results record the due and send times, the HTTP
status, the streamed token ids with the monotonic time each arrived, the
finish reason and any error. Time to first token is reckoned from the due
time, so a late send counts against the server's latency.

Adapted from benchmarks/loadgen.py (SSE client and open loop), kept here so
the yardstick does not move when the program's own load generator does.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Dict, List


async def _request(host: str, port: int, payload: Dict, res: Dict,
                   timeout_s: float) -> None:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = json.dumps(payload).encode("utf-8")
        writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      f"Connection: close\r\n\r\n").encode("latin-1") + body)
        await writer.drain()
        head = await asyncio.wait_for(reader.readuntil(b"\r\n\r\n"),
                                      timeout_s)
        res["status"] = int(head.decode("latin-1").split()[1])
        if res["status"] != 200:
            res["error"] = (await reader.read()).decode("utf-8",
                                                        "replace")[:300]
            return
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout_s)
            if not line:
                res["error"] = "stream closed before [DONE]"
                return
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                res["done"] = time.monotonic()
                return
            choice = json.loads(data)["choices"][0]
            if "token_id" in choice:
                res["stamps"].append(time.monotonic())
                res["tokens"].append(int(choice["token_id"]))
            if choice.get("finish_reason") is not None:
                res["finish"] = choice["finish_reason"]
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


# the error of a request still streaming at the schedule's deadline
CUT = "unfinished at the deadline"


def new_result(t0: float, due: float) -> Dict:
    return {"due": t0 + due, "sent": None, "status": 0, "tokens": [],
            "stamps": [], "finish": None, "done": None, "error": None}


async def fire(host: str, port: int, payload: Dict, res: Dict,
               timeout_s: float) -> Dict:
    """Send one request at its due time `res["due"]` and fill `res`."""
    delay = res["due"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    res["sent"] = time.monotonic()
    try:
        await _request(host, port, payload, res, timeout_s)
    except (asyncio.TimeoutError, ConnectionError, OSError,
            asyncio.IncompleteReadError) as e:
        res["error"] = f"{type(e).__name__}: {e}"
    return res


async def run_schedule(sched: Dict) -> List[Dict]:
    """Every request of the schedule; those still streaming at the
    schedule's `deadline` are cut off and marked unfinished."""
    results = [new_result(sched["t0"], r["due"]) for r in sched["requests"]]
    tasks = [asyncio.create_task(fire(sched["host"], sched["port"],
                                      r["payload"], res, sched["timeout_s"]))
             for r, res in zip(sched["requests"], results)]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, sched["deadline"] - time.monotonic()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for t, res in zip(tasks, results):
        if t in pending:
            res["error"] = CUT
    return results


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        sched = json.load(f)
    results = asyncio.run(run_schedule(sched))
    with open(argv[1], "w") as f:
        json.dump(results, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
