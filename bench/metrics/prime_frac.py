"""prime_frac: the share of the traced window in which the scheduler was
priming a request, in percent: the union of the program's `sched.prime`
host spans (`repro.serve.scheduler.metrics.span`, on the profiler's
clock) inside `bench.window`, over the window. No decode step is
dispatched while a prime runs, so every stream waits through it.

A program without pump spans (no `sched.tick` in the trace) reads nothing;
one that has them and primed nothing in the window reads 0."""
from __future__ import annotations

from bench import trace_reduce as tr


def read(ctx):
    trace = ctx.get("trace")
    if ctx.get("kind") != "serve" or trace is None or trace.window_s <= 0:
        return None
    if not any(o.name == "sched.tick" for o in trace.host):
        return None
    primes = tr.union(tr.clip(((o.start, o.end) for o in trace.host
                               if o.name == "sched.prime"), *trace.window))
    return 100.0 * tr.total(primes) * 1e-9 / trace.window_s
