"""The trace reduction on a slice of a real device trace (one TPU v5e,
qwen3-4b training, 150 ms around the FourierFT ΔW forward kernel),
checked against a plain timeline count."""
from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr
from bench.metrics._kernels import (FOURIER_DELTAW, FOURIER_DELTAW_FWD,
                                    FOURIER_DELTAW_GRAD, PAGED_ATTENTION)

FIXTURE = Path(__file__).with_name("fixtures") / "trace_train_slice.json"
RES = 100.0                     # ns per timeline bin


@pytest.fixture(scope="module")
def trace():
    return json.loads(FIXTURE.read_text())


def _timeline(ops, lo, hi):
    busy = np.zeros(int((hi - lo) / RES) + 1, bool)
    for _, s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            busy[int(np.ceil((a - lo) / RES)):int((b - lo) / RES)] = True
    return busy


def test_busy_and_idle_match_a_timeline_count(trace):
    s = tr.from_json(trace)
    lo, hi = trace["window"]
    ops = trace["devices"][0]["ops"]
    busy = _timeline(ops, lo, hi).sum() * RES * 1e-9
    assert s.busy_s == pytest.approx(busy, rel=2e-3)
    assert s.idle_frac() == pytest.approx(1 - busy / ((hi - lo) * 1e-9),
                                          abs=2e-3)


def test_kernel_time_by_signature(trace):
    s = tr.from_json(trace)
    lo, hi = trace["window"]
    ops = trace["devices"][0]["ops"]
    fwd = [o for o in ops if re.search(FOURIER_DELTAW_FWD, o[0])]
    assert fwd, "the slice holds a ΔW forward kernel"
    want = sum(e - s_ for n, s_, e in ops if re.search(FOURIER_DELTAW, n)
               and s_ >= lo and e <= hi) * 1e-9
    assert s.op_seconds(FOURIER_DELTAW) == pytest.approx(want)
    assert not any(re.search(PAGED_ATTENTION, o[0]) for o in ops)
    assert not any(re.search(FOURIER_DELTAW_GRAD, o[0]) for o in fwd)


def test_one_chip_has_no_exposed_collectives(trace):
    assert tr.from_json(trace).exposed_collective_s() == 0.0
