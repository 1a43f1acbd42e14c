"""Names the program puts into its own traces and compiled programs, on the
CPU: the host spans of the scheduler round and the gateway pump (read back
from a real profiler trace, with their nesting and stats), the
`{op}.{method}` name scope of every registry-dispatched kernel op, and the
`name=` of every Pallas kernel as the TPU lowering carries it.

The benchmark's per-layer readers (`bench/metrics/`) find the spans and the
kernel names in traces; a rename here silences them."""
import asyncio
import glob
import json
import re

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.configs.base import PEFTConfig
from repro.core import adapter as adapter_api
from repro.core import peft as peft_mod
from repro.kernels import dct_deltaw, fourier_deltaw
from repro.kernels import paged_attention as pa
from repro.models import build
from repro.serve import AdapterBank, ContinuousScheduler, Engine, Request
from repro.serve.gateway import GatewayServer
from repro.serve.scheduler.metrics import span

PROF = PEFTConfig(method="fourierft", n=16, alpha=25.0,
                  param_dtype="float32")
PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17, 18, 19],
           [3, 1, 4, 1, 5, 9], [2, 7, 1, 8]]


def _engine(slots=2):
    """A tiny paged-serving engine with one resident FourierFT tenant."""
    cfg = C.reduced(C.get("yi-6b")).replace(vocab=64, param_dtype="float32",
                                            dtype="float32")
    model = build(cfg, PEFTConfig(method="none"))
    params = model.init(jax.random.PRNGKey(0))
    tree = peft_mod.init_adapters(jax.random.PRNGKey(1), model.sites, PROF)
    keep = set(adapter_api.resolve("fourierft").trainable_leaves(PROF))
    tree = {s: {k: v + 0.05 for k, v in d.items() if k in keep}
            for s, d in tree.items()}
    bank = AdapterBank(model, {"fourierft": PROF}, capacity=2)
    bank.load("t0", tree, PROF)
    return Engine(model, params, batch_slots=slots, max_len=48, bank=bank)


def _requests():
    return [Request(prompt=jnp.array(p, jnp.int32), max_new=3 + i,
                    adapter_id="t0" if i % 2 == 0 else None)
            for i, p in enumerate(PROMPTS)]


def _host_spans(trace_dir, names):
    """{thread line name: [(name, start_ns, end_ns, stats)]} of the host
    events named in `names`, from the trace's xplane."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(path[-1])
    out = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(line.name, []).append(
                        (ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def _traced(trace_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def _inside(child, parents):
    """The span of `parents` that holds `child` (None if none does)."""
    for p in parents:
        if p[1] <= child[1] and child[2] <= p[2]:
            return p
    return None


SCHED = ("sched.tick", "sched.admit", "sched.prime", "sched.decode",
         "sched.drain")
GATEWAY = ("gateway.commands", "gateway.dispatch", "gateway.idle")


def test_span_yields_elapsed_seconds():
    with span("test.span", k=1) as s:
        assert s.seconds == 0.0
        s.annotate(late=2)              # no trace running: a no-op
        sum(range(10000))
    assert s.seconds > 0.0


def test_scheduler_spans_nest_and_count_primes(tmp_path):
    sched = ContinuousScheduler(_engine(), page_size=8)
    reqs = _requests()
    _traced(tmp_path, lambda: sched.serve(reqs, arrivals=[0, 0, 1, 2, 6]))
    lines = _host_spans(tmp_path, SCHED)
    assert len(lines) == 1, "every span is on the scheduler's thread"
    spans = next(iter(lines.values()))
    by = {n: [s for s in spans if s[0] == n] for n in SCHED}
    assert all(by[n] for n in SCHED), {n: len(v) for n, v in by.items()}

    primed = {rid: r.prime_s for rid, r in sched.metrics.requests.items()
              if r.prime_s is not None}
    assert len(by["sched.prime"]) == len(primed) == len(reqs)
    assert {s[3]["rid"] for s in by["sched.prime"]} == set(primed)
    assert {s[3]["rid"] for s in by["sched.admit"]} == set(primed)
    for s in by["sched.prime"]:
        # one clock pair: the span's own length is what on_prime recorded
        assert (s[2] - s[1]) * 1e-9 == pytest.approx(primed[s[3]["rid"]],
                                                     abs=2e-3)
        assert s[3]["bucket"] in (8, 16)
        admit = _inside(s, by["sched.admit"])
        assert admit is not None and admit[3]["rid"] == s[3]["rid"]
    for s in by["sched.admit"] + by["sched.decode"]:
        assert _inside(s, by["sched.tick"]) is not None
    for s in by["sched.drain"]:
        assert _inside(s, by["sched.decode"]) is not None
        assert s[3]["steps"] in (1, 2)   # the step behind; + the newest
    assert all(1 <= s[3]["active"] <= 2 for s in by["sched.decode"])
    assert len(by["sched.decode"]) == sched.metrics.steps


def _gateway_session(server):
    async def main():
        await server.start("127.0.0.1", 0)
        try:
            async def one(prompt):
                body = json.dumps({"model": "adapter:t0", "prompt": prompt,
                                   "max_tokens": 3, "stream": True}).encode()
                reader, writer = await asyncio.open_connection(
                    server.host, server.port)
                writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                              f"Content-Length: {len(body)}\r\n"
                              "Connection: close\r\n\r\n").encode() + body)
                await writer.drain()
                raw = await reader.read()
                writer.close()
                return raw
            raws = await asyncio.gather(*(one(p) for p in PROMPTS[:3]))
            await asyncio.sleep(0.05)                 # an idle pump round
            return raws
        finally:
            await server.close()
    return asyncio.run(main())


def test_gateway_pump_spans(tmp_path):
    server = GatewayServer(ContinuousScheduler(_engine(), page_size=8),
                           default_max_new=3)
    raws = _traced(tmp_path, lambda: _gateway_session(server))
    assert all(r.startswith(b"HTTP/1.1 200") and b"[DONE]" in r
               for r in raws)
    lines = _host_spans(tmp_path, GATEWAY + ("sched.tick",))
    pump = [v for v in lines.values() if any(s[0] in GATEWAY for s in v)]
    assert len(pump) == 1, "the pump runs on one thread"
    spans = sorted(pump[0], key=lambda s: s[1])
    assert {s[0] for s in spans} == set(GATEWAY) | {"sched.tick"}
    # the pump's spans are siblings: one after another, never overlapping
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1], (a[:3], b[:3])


# ---------------------------------------------------------------------------
# names inside compiled programs
# ---------------------------------------------------------------------------

def _shapes(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def test_decode_step_metadata_names_bank_and_attention():
    """The compiled decode step keeps its module name (the benchmark reads
    `jit_decode_step`) and carries the registry scopes of the bank apply
    and paged attention in its op metadata; so does the paged prime."""
    sched = ContinuousScheduler(_engine(), page_size=8)
    seen = {}

    def record(name, fn):
        def call(*args):
            seen.setdefault(name, (fn, _shapes(args)))
            return fn(*args)
        return call
    sched._decode = record("decode", sched._decode)
    sched._prefill_paged = record("prefill", sched._prefill_paged)
    sched.serve(_requests()[:2])
    texts = {k: fn.lower(*args).compile().as_text()
             for k, (fn, args) in seen.items()}
    assert re.search(r"^HloModule jit_decode_step\b", texts["decode"], re.M)
    assert re.search(r"^HloModule jit_prefill_paged\b", texts["prefill"],
                     re.M)
    for scope in ("bank_apply.fourierft", "paged_attention.attention"):
        assert re.search(rf'op_name="[^"]*/{re.escape(scope)}/',
                         texts["decode"]), scope
    assert re.search(r'op_name="[^"]*/bank_apply\.fourierft/',
                     texts["prefill"])


def test_kernel_op_call_runs_under_its_scope():
    """Calling a KernelOp runs its fn under the `{op}.{method}` scope."""
    from repro.kernels import api
    op = api.KernelOp("bank_apply", "probe", "einsum", jnp.sin)
    text = jax.jit(op).lower(jnp.ones(4)).compile().as_text()
    assert re.search(r'op_name="[^"]*/bank_apply\.probe/sin"', text)


S = jax.ShapeDtypeStruct
KERNELS = {
    "fourier_deltaw_fwd": (
        lambda c, u, v: fourier_deltaw.deltaw_pallas(c, u, v, 256, 512, 1.0),
        (S((2, 1, 128), jnp.float32), S((1, 128), jnp.int32),
         S((1, 128), jnp.int32))),
    "fourier_deltaw_coef_grad": (
        lambda g, u, v: fourier_deltaw.dc_pallas(g, u, v, 256, 512, 1.0),
        (S((2, 256, 512), jnp.float32), S((1, 128), jnp.int32),
         S((1, 128), jnp.int32))),
    "dct_deltaw_fwd": (
        lambda c, u, v: dct_deltaw.deltaw_pallas(c, u, v, 256, 512, 1.0),
        (S((2, 1, 128), jnp.float32), S((1, 128), jnp.int32),
         S((1, 128), jnp.int32))),
    "dct_deltaw_coef_grad": (
        lambda g, u, v: dct_deltaw.dc_pallas(g, u, v, 256, 512, 1.0),
        (S((2, 256, 512), jnp.float32), S((1, 128), jnp.int32),
         S((1, 128), jnp.int32))),
    "paged_attention": (
        pa.paged_attention_pallas,
        (S((4, 1, 32, 128), jnp.bfloat16), S((64, 16, 8, 128), jnp.bfloat16),
         S((64, 16, 8, 128), jnp.bfloat16), S((4, 16), jnp.int32),
         S((4,), jnp.int32))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_name_in_tpu_lowering(name):
    """Lowered for the TPU (no chip, no TPU compile): the Mosaic custom
    call carries the kernel's `name=` as its `kernel_name`."""
    fn, args = KERNELS[name]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert re.findall(r'kernel_name = "(\w+)"', text) == [name]
