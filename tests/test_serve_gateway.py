"""OpenAI-compatible gateway over the continuous runtime (DESIGN.md
§Gateway): SSE streams bit-identical to the in-process replay (including
a heterogeneous fourierft+lora+base tenant mix), 429 backpressure under
saturation with a successful retry, mid-stream client disconnect leaving
zero leaked slots/pages/bank-pins, request validation 400s/404s, the
/v1/models and /metrics endpoints, and the scheduler-side cancel path +
monotonic cumulative counters the gateway leans on."""
import asyncio
import json

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.checkpoint import adapters as adapter_ckpt
from repro.configs.base import PEFTConfig
from repro.core import adapter as adapter_api
from repro.core import peft as peft_mod
from repro.models import build
from repro.serve import AdapterBank, ContinuousScheduler, Engine, Request
from repro.serve.gateway import GatewayServer
from repro.serve.gateway.protocol import (
    ApiError, parse_request, prometheus_text, resolve_model,
)


def _cfg():
    return C.reduced(C.get("yi-6b")).replace(vocab=64, param_dtype="float32",
                                             dtype="float32")


def _base_model():
    model = build(_cfg(), PEFTConfig(method="none"))
    return model, model.init(jax.random.PRNGKey(0))


def _export_tenants(model, directory):
    profiles = {
        "fourierft": PEFTConfig(method="fourierft", n=16, alpha=25.0,
                                param_dtype="float32"),
        "lora": PEFTConfig(method="lora", lora_r=2, param_dtype="float32"),
    }
    for i, (tid, m) in enumerate(zip(("t-fft", "t-lora"),
                                     ("fourierft", "lora"))):
        prof = profiles[m]
        tree = peft_mod.init_adapters(jax.random.PRNGKey(10 + i),
                                      model.sites, prof)
        tree = jax.tree.map(
            lambda x: x + 0.05 if jnp.issubdtype(x.dtype, jnp.floating)
            else x, tree)
        trainable = set(adapter_api.resolve(m).trainable_leaves(prof))
        tree = {s: {k: v for k, v in d.items() if k in trainable}
                for s, d in tree.items()}
        adapter_ckpt.export_adapter(str(directory), tid, tree, prof)
    return profiles


def _server(model, params, *, slots=2, max_len=48, bank=None, **kw):
    eng = Engine(model, params, batch_slots=slots, max_len=max_len,
                 bank=bank)
    return GatewayServer(ContinuousScheduler(eng, page_size=8), **kw)


# ---- stdlib test client ----------------------------------------------------
async def _raw(host, port, data: bytes):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(data)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, body


async def _post(host, port, path, payload):
    body = payload if isinstance(payload, bytes) \
        else json.dumps(payload).encode()
    return await _raw(host, port,
                      (f"POST {path} HTTP/1.1\r\nHost: t\r\n"
                       f"Content-Length: {len(body)}\r\n"
                       f"Connection: close\r\n\r\n").encode() + body)


async def _get(host, port, path):
    return await _raw(host, port, (f"GET {path} HTTP/1.1\r\nHost: t\r\n"
                                   "Connection: close\r\n\r\n").encode())


def _sse_parse(body: bytes):
    """SSE body -> (token ids, finish_reason, saw [DONE])."""
    tokens, finish, done = [], None, False
    for line in body.split(b"\n"):
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        data = line[len(b"data: "):]
        if data == b"[DONE]":
            done = True
            continue
        choice = json.loads(data)["choices"][0]
        if "token_id" in choice:
            tokens.append(int(choice["token_id"]))
        if choice.get("finish_reason") is not None:
            finish = choice["finish_reason"]
    return tokens, finish, done


def _completion(model, prompt, max_new, stream=True):
    return {"model": model, "prompt": prompt, "max_tokens": max_new,
            "stream": stream}


async def _drain_idle(server, timeout=10.0):
    """Wait until the scheduler has no active slots (pump-thread read)."""
    deadline = asyncio.get_event_loop().time() + timeout
    sched = server.sched
    while await server.bridge.call(lambda: sched.slots.any_active()):
        assert asyncio.get_event_loop().time() < deadline, "never drained"
        await asyncio.sleep(0.01)


# ---------------------------------------------------------------------------
# protocol units (no server)
# ---------------------------------------------------------------------------
class TestProtocol:
    def _parse(self, payload, kind="completion", **kw):
        kw.setdefault("vocab", 64)
        kw.setdefault("max_len", 48)
        kw.setdefault("default_max_new", 8)
        kw.setdefault("base_aliases", ())
        return parse_request(kind, payload, **kw)

    def test_validation_rejections(self):
        cases = [
            ({"model": "base"}, 400),                       # no prompt
            ({"model": "base", "prompt": []}, 400),         # empty
            ({"model": "base", "prompt": [1, 2], "n": 2}, 400),
            ({"model": "base", "prompt": [1, 999]}, 400),   # id >= vocab
            ({"model": "base", "prompt": [1, -2]}, 400),    # negative id
            ({"model": "base", "prompt": [1.5]}, 400),      # non-int id
            ({"model": "base", "prompt": [1],
              "max_tokens": 0}, 400),
            ({"model": "base", "prompt": [1],
              "stream": "yes"}, 400),
            ({"model": "base", "prompt": list(range(1, 47)),
              "max_tokens": 30}, 400),                      # cache overflow
            ({"model": 7, "prompt": [1]}, 400),
            ({"model": "oops", "prompt": [1]}, 404),
        ]
        for payload, status in cases:
            with pytest.raises(ApiError) as ei:
                self._parse(payload)
            assert ei.value.status == status, payload

    def test_chat_needs_messages(self):
        with pytest.raises(ApiError):
            self._parse({"model": "base"}, kind="chat")
        preq = self._parse({"model": "base",
                            "messages": [{"role": "user", "content": "hi"}]},
                           kind="chat")
        assert preq.prompt and all(0 <= t < 64 for t in preq.prompt)

    def test_resolve_model(self):
        assert resolve_model("base") is None
        assert resolve_model("yi-6b-smoke", ("yi-6b-smoke",)) is None
        assert resolve_model("adapter:t0") == "t0"
        with pytest.raises(ApiError) as ei:
            resolve_model("gpt-4")
        assert ei.value.status == 404
        with pytest.raises(ApiError):
            resolve_model("adapter:")

    def test_prometheus_text(self):
        text = prometheus_text(
            {"requests_admitted_total": 3, "queue_depth": 1.0},
            labeled={"gateway_responses_total": {'code="200"': 4}})
        assert "# TYPE repro_requests_admitted_total counter" in text
        assert "# TYPE repro_queue_depth gauge" in text
        assert "repro_requests_admitted_total 3" in text
        assert 'repro_gateway_responses_total{code="200"} 4' in text


# ---------------------------------------------------------------------------
# scheduler cancel path + cumulative counters (no HTTP)
# ---------------------------------------------------------------------------
class TestSchedulerCancel:
    def test_cancel_queued_request(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=1, max_len=48)
        sched = ContinuousScheduler(eng, page_size=8)
        r = Request(prompt=jnp.array([1, 2, 3], jnp.int32), max_new=4)
        rid = sched.submit(r)
        assert sched.cancel(rid) is True
        assert sched.cancel(rid) is False      # already gone
        assert len(sched.queue) == 0
        assert r.out == []
        s = sched.metrics.summary()
        assert s["requests_cancelled_total"] == 1.0
        assert s["queue_depth"] == 0.0

    def test_cancel_active_frees_slot_and_pages(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        sched = ContinuousScheduler(eng, page_size=8)
        long = Request(prompt=jnp.array([1, 2, 3, 4], jnp.int32), max_new=24)
        rid = sched.submit(long)
        for _ in range(6):                     # admit + buffer some decode
            sched.tick()
        assert sched.slots.any_active()
        assert sched.cancel(rid) is True       # abort with work in flight
        assert not sched.slots.any_active()
        sched.pager.assert_no_leaks()
        # the drained partial (here: the prime token) lands on the request
        assert 0 < len(long.out) < 24
        # the runtime stays healthy: a follow-up request is exact
        follow = Request(prompt=jnp.array([7, 8, 9], jnp.int32), max_new=5)
        sched.serve([follow])
        ref = eng.generate([follow.prompt], max_new=5)[0]
        assert follow.out == [int(t) for t in jnp.asarray(ref).reshape(-1)]
        sched.pager.assert_no_leaks()

    def test_counters_survive_reset(self):
        model, params = _base_model()
        eng = Engine(model, params, batch_slots=2, max_len=48)
        sched = ContinuousScheduler(eng, page_size=8)
        sched.serve([Request(prompt=jnp.array([1, 2], jnp.int32), max_new=3)
                     for _ in range(2)])
        before = sched.metrics.summary()
        assert before["requests_finished_total"] == 2.0
        sched.reset_metrics()                  # scrape-window reset
        after = sched.metrics.summary()
        for k in ("requests_submitted_total", "requests_admitted_total",
                  "requests_finished_total", "tokens_emitted_total"):
            assert after[k] == before[k], k    # counters are cumulative
        sched.serve([Request(prompt=jnp.array([5], jnp.int32), max_new=2)])
        assert sched.metrics.summary()["requests_finished_total"] == 3.0


# ---------------------------------------------------------------------------
# HTTP end-to-end
# ---------------------------------------------------------------------------
class TestGatewayHTTP:
    def test_streams_bit_identical_heterogeneous(self, tmp_path):
        """Concurrent SSE streams over a fourierft+lora+base mix equal the
        in-process scheduler replay token for token."""
        model, params = _base_model()
        profiles = _export_tenants(model, tmp_path)

        def bank():
            return AdapterBank(model, profiles, capacity=4,
                               checkpoint_dir=str(tmp_path))

        prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12],
                   [3, 1, 4, 1, 5], [2, 7, 1, 8], [6, 6, 6]]
        models = ["adapter:t-fft", "adapter:t-lora", "base",
                  "adapter:t-fft", "adapter:t-lora", "base"]

        async def drive():
            server = _server(model, params, slots=3, bank=bank())
            await server.start()
            try:
                return await asyncio.gather(*(
                    _post(server.host, server.port, "/v1/completions",
                          _completion(m, p, 6))
                    for m, p in zip(models, prompts)))
            finally:
                await server.close()

        responses = asyncio.run(drive())
        got = []
        for status, _, body in responses:
            assert status == 200
            tokens, finish, done = _sse_parse(body)
            assert done and finish == "length"
            got.append(tokens)
        # replay the same traffic through a fresh scheduler, no HTTP
        replay_eng = Engine(model, params, batch_slots=3, max_len=48,
                            bank=bank())
        reqs = [Request(prompt=jnp.array(p, jnp.int32), max_new=6,
                        adapter_id=resolve_model(m))
                for m, p in zip(models, prompts)]
        ContinuousScheduler(replay_eng, page_size=8).serve(reqs)
        assert got == [r.out for r in reqs]

    def test_blocking_json_matches_stream(self):
        model, params = _base_model()

        async def drive():
            server = _server(model, params)
            await server.start()
            try:
                s1, _, b1 = await _post(server.host, server.port,
                                        "/v1/completions",
                                        _completion("base", [1, 2, 3], 5))
                s2, _, b2 = await _post(
                    server.host, server.port, "/v1/completions",
                    _completion("base", [1, 2, 3], 5, stream=False))
                return s1, b1, s2, b2
            finally:
                await server.close()

        s1, b1, s2, b2 = asyncio.run(drive())
        assert s1 == 200 and s2 == 200
        stream_tokens, _, _ = _sse_parse(b1)
        obj = json.loads(b2)
        choice = obj["choices"][0]
        assert choice["token_ids"] == stream_tokens
        assert choice["finish_reason"] == "length"
        assert obj["usage"]["completion_tokens"] == len(stream_tokens)

    def test_429_under_saturation_then_retry(self):
        """One slot + max_queue=1: a third request bounces with 429 and
        Retry-After while the runtime is saturated, then succeeds once the
        backlog drains."""
        model, params = _base_model()

        async def drive():
            server = _server(model, params, slots=1, max_queue=1,
                             retry_after_s=0.25)
            await server.start()
            host, port = server.host, server.port
            try:
                a = asyncio.ensure_future(_post(
                    host, port, "/v1/completions",
                    _completion("base", [1, 2, 3], 24)))
                b = asyncio.ensure_future(_post(
                    host, port, "/v1/completions",
                    _completion("base", [4, 5], 24, stream=False)))
                # probe only once a holds the slot and b waits in the queue:
                # a probe that reached the server before b would take the
                # queue place itself and leave the 429 to b
                sched = server.sched
                deadline = asyncio.get_event_loop().time() + 10.0
                while not await server.bridge.call(
                        lambda: len(sched.queue) >= 1
                        and sched.slots.any_active()):
                    assert asyncio.get_event_loop().time() < deadline, \
                        "a and b never filled the slot and the queue"
                    await asyncio.sleep(0.002)
                saw_429, retry_after = False, None
                for _ in range(100):           # while a+b occupy slot+queue
                    status, headers, _ = await _post(
                        host, port, "/v1/completions",
                        _completion("base", [6], 2, stream=False))
                    if status == 429:
                        saw_429 = True
                        retry_after = headers.get("retry-after")
                        break
                    await asyncio.sleep(0.005)
                (sa, _, _), (sb, _, _) = await asyncio.gather(a, b)
                await _drain_idle(server)
                sc, _, body = await _post(     # the retry goes through
                    host, port, "/v1/completions",
                    _completion("base", [6], 2, stream=False))
                metrics = await server.bridge.call(
                    lambda: server.sched.metrics.summary())
                return saw_429, retry_after, sa, sb, sc, body, metrics
            finally:
                await server.close()

        saw_429, retry_after, sa, sb, sc, body, metrics = asyncio.run(drive())
        assert saw_429 and retry_after is not None
        assert float(retry_after) == 0.25
        assert (sa, sb, sc) == (200, 200, 200)
        assert len(json.loads(body)["choices"][0]["token_ids"]) == 2
        assert metrics["requests_rejected_total"] >= 1.0

    def test_disconnect_mid_stream_leaks_nothing(self, tmp_path):
        """Abruptly closing the socket mid-stream cancels the request:
        every slot returns to FREE, the page pool balances, the tenant's
        bank row unpins, and the next request is exact."""
        model, params = _base_model()
        profiles = _export_tenants(model, tmp_path)

        async def drive():
            bank = AdapterBank(model, profiles, capacity=4,
                               checkpoint_dir=str(tmp_path))
            server = _server(model, params, slots=2, bank=bank)
            await server.start()
            host, port = server.host, server.port
            try:
                reader, writer = await asyncio.open_connection(host, port)
                body = json.dumps(_completion(
                    "adapter:t-fft", [1, 2, 3, 4], 32)).encode()
                writer.write((f"POST /v1/completions HTTP/1.1\r\n"
                              f"Host: t\r\nContent-Length: {len(body)}\r\n"
                              f"Connection: close\r\n\r\n").encode() + body)
                await writer.drain()
                await reader.readuntil(b"\r\n\r\n")      # response head
                await reader.readuntil(b"\n\n")          # >= 1 SSE frame
                writer.close()                           # walk away
                await _drain_idle(server)
                sched = server.sched
                state = await server.bridge.call(lambda: (
                    sched.slots.active_slots(),
                    sched.slots.adapter_ids(),
                    sched.metrics.summary()["requests_cancelled_total"]))
                await server.bridge.call(sched.pager.assert_no_leaks)
                # runtime still serves exactly after the abort
                status, _, resp = await _post(
                    host, port, "/v1/completions",
                    _completion("adapter:t-lora", [7, 8, 9], 4,
                                stream=False))
                await server.bridge.call(sched.pager.assert_no_leaks)
                return state, status, json.loads(resp)
            finally:
                await server.close()

        (active, pins, cancelled), status, resp = asyncio.run(drive())
        assert active == [] and pins == [None, None]
        assert cancelled >= 1.0
        assert status == 200
        ref_eng = Engine(model, params, batch_slots=2, max_len=48,
                         bank=AdapterBank(model, profiles, capacity=4,
                                          checkpoint_dir=str(tmp_path)))
        ref_eng.bank.load_from_checkpoint("t-lora")
        ref = ref_eng.generate([jnp.array([7, 8, 9], jnp.int32)],
                               max_new=4, adapter_ids=["t-lora"])[0]
        assert resp["choices"][0]["token_ids"] \
            == [int(t) for t in jnp.asarray(ref).reshape(-1)]

    def test_request_timeout_504(self):
        model, params = _base_model()

        async def drive():
            server = _server(model, params, request_timeout_s=1e-4)
            await server.start()
            try:
                status, _, body = await _post(
                    server.host, server.port, "/v1/completions",
                    _completion("base", [1, 2, 3], 16, stream=False))
                await _drain_idle(server)
                await server.bridge.call(server.sched.pager.assert_no_leaks)
                return status, body
            finally:
                await server.close()

        status, body = asyncio.run(drive())
        assert status == 504
        assert json.loads(body)["error"]["type"] == "timeout_error"

    def test_malformed_requests(self):
        model, params = _base_model()

        async def drive():
            server = _server(model, params)
            await server.start()
            host, port = server.host, server.port
            try:
                return [
                    await _post(host, port, "/v1/completions",
                                b"{not json"),
                    await _post(host, port, "/v1/completions",
                                {"model": "base"}),
                    await _post(host, port, "/v1/completions",
                                {"model": "base", "prompt": [1], "n": 2}),
                    await _post(host, port, "/v1/completions",
                                {"model": "base", "prompt": [999]}),
                    await _post(host, port, "/v1/completions",
                                {"model": "base",
                                 "prompt": list(range(1, 50)),
                                 "max_tokens": 16}),
                    await _post(host, port, "/v1/completions",
                                {"model": "gpt-4", "prompt": [1]}),
                    await _post(host, port, "/v1/completions",
                                {"model": "adapter:ghost", "prompt": [1]}),
                    await _get(host, port, "/nope"),
                ]
            finally:
                await server.close()

        results = asyncio.run(drive())
        statuses = [r[0] for r in results]
        assert statuses == [400, 400, 400, 400, 400, 404, 404, 404]
        for status, _, body in results[:5]:
            assert json.loads(body)["error"]["type"] \
                == "invalid_request_error"

    def test_models_and_metrics_endpoints(self, tmp_path):
        model, params = _base_model()
        profiles = _export_tenants(model, tmp_path)

        async def drive():
            bank = AdapterBank(model, profiles, capacity=4,
                               checkpoint_dir=str(tmp_path))
            bank.load_from_checkpoint("t-fft")
            server = _server(model, params, bank=bank)
            await server.start()
            host, port = server.host, server.port
            try:
                await _post(host, port, "/v1/chat/completions",
                            {"model": "base", "stream": False,
                             "messages": [{"role": "user",
                                           "content": "hi"}],
                             "max_tokens": 3})
                ms, _, mbody = await _get(host, port, "/v1/models")
                ps, _, pbody = await _get(host, port, "/metrics")
                hs, _, _ = await _get(host, port, "/healthz")
                return ms, mbody, ps, pbody, hs
            finally:
                await server.close()

        ms, mbody, ps, pbody, hs = asyncio.run(drive())
        assert (ms, ps, hs) == (200, 200, 200)
        ids = [m["id"] for m in json.loads(mbody)["data"]]
        assert "base" in ids and "adapter:t-fft" in ids
        text = pbody.decode()
        assert "# TYPE repro_requests_admitted_total counter" in text
        assert "repro_requests_admitted_total 1" in text
        assert "repro_requests_finished_total 1" in text
        assert "repro_gateway_page_free_frac" in text
        assert 'repro_gateway_responses_total{code="200"}' in text
