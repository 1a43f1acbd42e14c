"""The benchmark's own arithmetic: interval reduction of a device trace,
FLOP and byte counts, due-time latency and nearest-rank tails."""
from __future__ import annotations

import math

import pytest

from bench import trace_reduce as tr
from bench.drivers import serve
from bench.metrics import _flops


def _op(name, s, e):
    return tr.Op(name, s, e, name, tr.opcode(name))


def _summary(ops_by_device, window, host=()):
    devs = [tr.DeviceTrace(f"/device:TPU:{i}", [_op(*o) for o in ops])
            for i, ops in enumerate(ops_by_device)]
    return tr.TraceSummary(devs, window, [tr.Op(n, s, e, n)
                                          for n, s, e in host])


FUSION = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)"
LOOP = "%while.1 = (s32[]) while((s32[]) %t), body=%b"
AR = "%all-reduce.2 = bf16[8]{0} all-reduce(bf16[8]{0} %x), to_apply=%add"


def test_busy_union_and_idle_share():
    # overlapping and nested ops count once; the window clips them
    s = _summary([[(LOOP, 0, 100), (FUSION, 10, 20), (FUSION, 150, 170),
                   (FUSION, 190, 260)]], (0, 200))
    assert s.busy_s == pytest.approx((100 + 20 + 10) * 1e-9)
    assert s.idle_frac() == pytest.approx(1 - 130 / 200)
    assert s.window_s == pytest.approx(200e-9)


def test_busy_is_averaged_over_devices():
    s = _summary([[(FUSION, 0, 50)], [(FUSION, 0, 150)]], (0, 200))
    assert s.busy_s == pytest.approx(100e-9)


def test_op_seconds_by_pattern():
    k = "%jvp.3 = f32[2,8,8]{} custom-call(f32[2,1,8]{} %c), " \
        "custom_call_target=\"tpu_custom_call\""
    s = _summary([[(k, 0, 30), (FUSION, 30, 40), (k, 50, 75)]], (0, 100))
    assert s.op_seconds("tpu_custom_call") == pytest.approx(55e-9)
    assert s.op_seconds("nothing-like-this") == 0.0


def test_exposed_collectives_ignore_container_ops():
    # the all-reduce sits inside a while loop: the loop does not hide it,
    # the fusion from 40 to 50 does
    s = _summary([[(LOOP, 0, 100), (AR, 30, 60), (FUSION, 40, 50)]],
                 (0, 100))
    assert s.exposed_collective_s() == pytest.approx(20e-9)


def test_idle_gaps_named_by_innermost_host_span():
    s = _summary([[(FUSION, 0, 10), (FUSION, 40, 50)]], (0, 60),
                 host=[("bench.window", 0, 60), ("outer", 5, 45),
                       ("inner", 15, 35)])
    gaps = dict(s.breakdown()["idle_gaps"])
    assert gaps == {"inner": pytest.approx(30e-9),
                    "no host span": pytest.approx(10e-9)}


def test_opcode_and_short_name():
    assert tr.opcode(LOOP) == "while"
    assert tr.opcode(FUSION) == "fusion"
    assert tr.short_name(FUSION) == "%fusion.1 fusion bf16[8]{0}"


TINY = {"config": {"hidden_size": 8, "num_attention_heads": 2,
                   "num_key_value_heads": 1, "head_dim": 4,
                   "intermediate_size": 16, "num_hidden_layers": 3,
                   "vocab_size": 10}}


def test_matmul_weights_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert _flops.matmul_weights(TINY) == 3 * per_layer + 8 * 10


def test_train_flops_per_token_by_hand():
    seq = 4
    weights = _flops.matmul_weights(TINY)
    # q·kᵀ and p·v: 2 FLOPs x 2 products x heads x head_dim x context
    attn = 3 * 4 * 2 * 4 * (seq + 1) / 2
    assert _flops.forward_flops_per_token(TINY, seq) == 2 * weights + attn
    assert _flops.train_flops_per_token(TINY, seq) == \
        2 * (2 * weights + attn)


def test_deltaw_work_by_hand():
    f, b = _flops.deltaw_work(3, 5, 7, 2)
    assert f == 4 * 3 * 5 * 7 * 2
    assert b == 4 * 3 * 5 * 2


def test_nearest_rank():
    vals = list(range(1, 101))
    assert serve.nearest_rank(vals, 0.90) == 90
    assert serve.nearest_rank(vals, 0.99) == 99
    assert serve.nearest_rank(list(range(1, 11)), 0.99) == 10
    assert serve.nearest_rank([5.0], 0.5) == 5.0
    assert math.isnan(serve.nearest_rank([], 0.9))


class _P:
    def __init__(self, max_tokens):
        self.max_tokens = max_tokens
        self.prompt = [1, 2, 3]


def _res(due, sent, stamps, finish="length", status=200):
    return {"due": due, "sent": sent, "status": status,
            "tokens": [7] * len(stamps), "stamps": stamps, "finish": finish,
            "done": None, "error": None}


def test_ttft_counts_from_due_time_and_misses_count_high():
    results = [
        _res(10.0, 10.5, [11.0, 11.1, 11.3]),       # sent late: ttft 1.0
        _res(11.0, 11.0, [11.2, 11.4, 11.5]),       # ttft 0.2
        _res(12.0, 12.0, [12.1, 12.2]),              # 2 of 3 tokens: failed
        _res(30.0, 30.0, [30.1, 30.2, 30.3]),       # due after the window
    ]
    planned = [_P(3), _P(3), _P(3), _P(3)]
    m = serve.window_metrics(results, planned, 10.0, 20.0, eos=None,
                             miss_at=80.0)
    assert m["attempted"] == 3 and m["failed"] == 1
    # ttfts: 1.0, 0.2, 68.0 (the failure, from its due time to miss_at)
    assert m["ttft_p50_ms"] == pytest.approx(1000.0)
    assert m["ttft_p90_ms"] == pytest.approx(68000.0)
    # gaps of the finished window requests: .1 .2 .2 .1
    assert m["itl_p99_ms"] == pytest.approx(200.0)
    # tokens streamed in [10, 20): 3 + 3 + 2
    assert m["serve_tokens_per_s"] == pytest.approx(8 / 10)
    assert m["loadgen_lag_p99_ms"] == pytest.approx(500.0)


def test_a_stream_cut_at_the_deadline_is_not_a_failure():
    from bench import loadgen
    cut = _res(10.0, 10.0, [10.5, 10.8])
    cut["error"], cut["finish"] = loadgen.CUT, None
    silent = _res(11.0, 11.0, [])
    silent["error"] = loadgen.CUT
    m = serve.window_metrics([cut, silent], [_P(9), _P(9)], 10.0, 20.0,
                             eos=None, miss_at=80.0)
    assert m["attempted"] == 2 and m["failed"] == 1
    assert m["itl_p99_ms"] == pytest.approx(300.0)
    assert not serve._ok(cut, 9, None)


def test_eos_finish_needs_the_eos_token():
    ok = _res(0.0, 0.0, [0.1, 0.2], finish="stop")
    ok["tokens"] = [5, 9]
    assert serve._ok(ok, 10, eos=9)
    assert not serve._ok(ok, 10, eos=8)
