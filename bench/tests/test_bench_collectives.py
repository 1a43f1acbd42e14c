"""The collective readers — `collective_share` and `collective_exposed_frac` —
checked against a plain count: on synthetic traces whose answer is known,
on random traces against a timeline of 1 ns bins, on a slice of a real
traced run of `yi-9b.train-sft512.x4` (four TPU v5e), and on the one-chip
fixtures, where they read nothing."""
from __future__ import annotations

import json
import random
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench import trace_reduce as tr
from bench.metrics import _collectives as col

FIXTURES = Path(__file__).with_name("fixtures")
FOUR = FIXTURES / "trace_train_x4_slice.json"

MATMUL = "%fusion.{} = bf16[16,512,1024]{{2,1,0}} fusion(bf16[16,512,4096]{{2,1,0}} %p), kind=kOutput"
# a compute op whose operand is a collective's result: still compute
READS_AR = "%fusion.{} = bf16[16,512,4096]{{2,1,0}} fusion(bf16[16,512,4096]{{2,1,0}} %all-reduce.3), kind=kLoop"
ALL_REDUCE = "%all-reduce.{} = bf16[16,512,4096]{{2,1,0}} all-reduce(bf16[16,512,4096]{{2,1,0}} %fusion.2), replica_groups=[1,4]<=[4]"
AR_START = "%all-reduce-start.{} = bf16[4096]{{0}} all-reduce-start(bf16[4096]{{0}} %fusion.5), replica_groups=[1,4]<=[4]"
AR_DONE = "%all-reduce-done.{} = bf16[4096]{{0}} all-reduce-done(bf16[4096]{{0}} %all-reduce-start.1)"
ALL_TO_ALL = "%all-to-all.{} = bf16[12,4096,4,128]{{3,1,0,2}} all-to-all(bf16[12,4096,4,128]{{3,1,0,2}} %copy_bitcast_fusion.1), dimensions={{2}}"
WHILE = "%while.{} = (s32[], bf16[16,512,4096]{{2,1,0}}) while((s32[], bf16[16,512,4096]{{2,1,0}}) %tuple.1), condition=%cond, body=%body"


def _trace(*devices, window=None):
    spans = [s for d in devices for _, s, _ in d] + \
        [e for d in devices for _, _, e in d]
    lo, hi = window or (min(spans), max(spans))
    return tr.from_json({
        "window": [lo, hi], "host": [],
        "devices": [{"name": f"/device:TPU:{i}", "ops": ops, "modules": []}
                    for i, ops in enumerate(devices)]})


def _read(name, trace):
    return harness.load_reader(name)({"kind": "train", "trace": trace})


def test_compute_that_hides_a_collective():
    t = _trace([[MATMUL.format(1), 0, 100], [ALL_REDUCE.format(1), 20, 60]])
    assert _read("collective_share", t) == pytest.approx(40.0)
    assert _read("collective_exposed_frac", t) == 0.0


def test_a_collective_partly_exposed():
    t = _trace([[MATMUL.format(1), 0, 50], [ALL_REDUCE.format(1), 40, 90]])
    assert _read("collective_share", t) == pytest.approx(100 * 50 / 90)
    assert _read("collective_exposed_frac", t) == pytest.approx(100 * 40 / 90)


def test_a_container_is_busy_but_hides_nothing():
    t = _trace([[WHILE.format(1), 0, 200], [MATMUL.format(1), 0, 50],
                [ALL_REDUCE.format(1), 60, 100]])
    assert _read("collective_share", t) == pytest.approx(20.0)
    assert _read("collective_exposed_frac", t) == pytest.approx(20.0)


def test_an_op_that_reads_a_collective_is_compute():
    t = _trace([[ALL_REDUCE.format(1), 0, 40], [READS_AR.format(2), 20, 60]])
    assert col.is_collective(t.devices[0].ops[0])
    assert not col.is_collective(t.devices[0].ops[1])
    assert _read("collective_share", t) == pytest.approx(100 * 40 / 60)
    assert _read("collective_exposed_frac", t) == pytest.approx(100 * 20 / 60)


def test_async_halves_and_all_to_all_count():
    ops = [[AR_START.format(1), 0, 5], [MATMUL.format(1), 5, 50],
           [AR_DONE.format(1), 50, 60], [ALL_TO_ALL.format(1), 60, 80]]
    t = _trace(ops)
    assert [col.is_collective(o) for o in t.devices[0].ops] == \
        [True, False, True, True]
    assert _read("collective_share", t) == pytest.approx(100 * 35 / 80)
    assert _read("collective_exposed_frac", t) == pytest.approx(100 * 35 / 80)


def test_chips_are_summed():
    a = [[MATMUL.format(1), 0, 100], [ALL_REDUCE.format(1), 90, 110]]
    b = [[MATMUL.format(1), 0, 50], [ALL_REDUCE.format(1), 50, 70]]
    t = _trace(a, b)
    assert _read("collective_share", t) == pytest.approx(100 * 40 / 180)
    assert _read("collective_exposed_frac", t) == \
        pytest.approx(100 * 30 / 180)


def test_the_window_clips():
    t = _trace([[MATMUL.format(1), 0, 100], [ALL_REDUCE.format(1), 80, 140]],
               window=(50, 120))
    assert _read("collective_share", t) == pytest.approx(100 * 40 / 70)
    assert _read("collective_exposed_frac", t) == pytest.approx(100 * 20 / 70)


def _plain(trace, res=1.0):
    """(collective, exposed, busy) bins summed over the devices, from a
    timeline of `res` ns bins."""
    lo, hi = trace.window
    n = int((hi - lo) / res) + 1
    coll = exp = busy = 0
    for d in trace.devices:
        c, k, b = (np.zeros(n, bool) for _ in range(3))
        for o in d.ops:
            s = int(np.ceil((max(o.start, lo) - lo) / res))
            e = int((min(o.end, hi) - lo) / res)
            if e <= s:
                continue
            b[s:e] = True
            if col.is_collective(o):
                c[s:e] = True
            elif o.opcode not in tr.CONTAINERS:
                k[s:e] = True
        coll += c.sum()
        exp += (c & ~k).sum()
        busy += b.sum()
    return coll, exp, busy


@pytest.mark.parametrize("seed", range(6))
def test_random_traces_match_a_timeline_count(seed):
    rng = random.Random(seed)
    # odd seeds add compute ops whose operand names a collective
    reads_ar = seed % 2 == 1
    kinds = [MATMUL, ALL_REDUCE, AR_DONE, ALL_TO_ALL, WHILE] \
        + [READS_AR] * reads_ar
    devices = []
    for _ in range(rng.randint(1, 4)):
        ops = []
        for i in range(rng.randint(5, 40)):
            s = rng.randint(0, 2000)
            ops.append([rng.choice(kinds).format(i), s,
                        s + rng.randint(1, 300)])
        devices.append(ops)
    t = _trace(*devices, window=(100, 2100))
    coll, exp, busy = _plain(t)
    share = _read("collective_share", t)
    frac = _read("collective_exposed_frac", t)
    if not coll:
        assert share is None and frac is None
        return
    assert share == pytest.approx(100 * coll / busy)
    assert frac == pytest.approx(100 * exp / busy)
    # where no operand names a collective, exposed time is the trace
    # reduction's own `exposed_collective_s`
    if not reads_ar:
        assert frac == pytest.approx(
            100 * t.exposed_collective_s() / t.busy_s)


@pytest.mark.parametrize("fixture", ["trace_train_slice.json",
                                     "trace_serve_slice.json"])
def test_one_chip_reads_nothing(fixture):
    fx = json.loads((FIXTURES / fixture).read_text())
    if "labels" in fx:
        labels = fx.pop("labels")
        for d in fx["devices"]:
            for key in ("ops", "modules"):
                d[key] = [[labels[i], s, e] for i, s, e in d[key]]
    t = tr.from_json(fx)
    assert t.busy_s > 0
    assert _read("collective_share", t) is None
    assert _read("collective_exposed_frac", t) is None


@pytest.fixture(scope="module")
def four():
    """The four-chip fixture in `trace_reduce.from_json`'s form (labels kept
    once, in a table, cut short after each op's opcode)."""
    fx = json.loads(FOUR.read_text())
    labels = fx.pop("labels")
    for d in fx["devices"]:
        d["ops"] = [[labels[i], s, e] for i, s, e in d["ops"]]
    return tr.from_json(fx)


def test_real_four_chip_slice_matches_a_timeline_count(four):
    assert len(four.devices) == 4
    codes = {o.opcode for d in four.devices for o in d.ops}
    assert {"all-reduce", "all-to-all", "custom-call", "while"} <= codes
    coll, exp, busy = _plain(four, res=100.0)
    assert coll > 0, "the slice holds collectives"
    assert _read("collective_share", four) == pytest.approx(
        100 * coll / busy, rel=2e-3)
    assert _read("collective_exposed_frac", four) == pytest.approx(
        100 * exp / busy, rel=2e-3)
