"""The readers of the program's own names — `prime_frac` (host spans
`sched.prime`) and `paged_attention_roofline` (the kernel named
`paged_attention`) — checked against a plain count on a slice of a real
traced serving run (one TPU v5e, `qwen3-4b.serve-64tenants`), and against a
traced run of the program at a size the CPU holds.

The fixture keeps, for every device op of the slice, the label the trace
reduction builds (`trace_reduce._stat_label`: the op's HLO text, then the
stats that name it), cut short where the rest names nothing the readers
look for; the program's host spans in the slice; every `sched.prime` span
of the run's window with its request id, and the scheduler's `prime_s` of
those requests; and the token stamps and prompt lengths of the requests
streaming in the slice, on the host clock (`w0`, `w1` bound the slice)."""
from __future__ import annotations

import json
import re
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import harness
from bench import trace_reduce as tr

FIXTURES = Path(__file__).with_name("fixtures")
SERVE = FIXTURES / "trace_serve_slice.json"
TRAIN = FIXTURES / "trace_train_slice.json"


def _reader(name):
    return harness.load_reader(name)


@pytest.fixture(scope="module")
def serve():
    """The serve fixture in `trace_reduce.from_json`'s form (its labels are
    kept once, in a table, and the ops index them)."""
    fx = json.loads(SERVE.read_text())
    labels = fx.pop("labels")
    for d in fx["devices"]:
        for key in ("ops", "modules"):
            d[key] = [[labels[i], s, e] for i, s, e in d[key]]
    return fx


def _ctx(fx):
    c = fx["ctx"]
    return {"kind": "serve", "trace": tr.from_json(fx),
            "config": harness.load_json(harness.ROOT / "bench" / "configs"
                                        / "qwen3-4b.json"),
            "w0": c["w0"], "w1": c["w1"], "results": c["results"],
            "planned": [SimpleNamespace(prompt=[0] * P) for P in c["P"]],
            "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
            "sched": {"prime_s": list(fx["prime_s"].values())}}


def _in_window(fx, s, e):
    lo, hi = fx["window"]
    return s >= lo and e <= hi


def test_prime_frac_is_the_union_of_prime_spans(serve):
    lo, hi = serve["window"]
    primes = [(max(s, lo), min(e, hi)) for n, s, e in serve["host"]
              if n == "sched.prime" and e > lo and s < hi]
    assert primes, "the slice holds a prime"
    covered = set()
    for s, e in primes:                   # 1 us bins
        covered.update(range(int(s // 1000), int(e // 1000)))
    want = 100.0 * len(covered) * 1e-6 / ((hi - lo) * 1e-9)
    got = _reader("prime_frac")(_ctx(serve))
    assert got == pytest.approx(want, abs=0.05)


def test_prime_spans_agree_with_prime_ms_p90(serve):
    """The one timing of a prime: each `sched.prime` span lasts what the
    scheduler recorded as that request's `prime_s`, so `prime_ms_p90`
    reads the same from either."""
    spans = {rid: (s, e) for rid, s, e in serve["prime_spans"]}
    assert spans.keys() == {int(r) for r in serve["prime_s"]}
    for rid, sec in serve["prime_s"].items():
        s, e = spans[int(rid)]
        assert (e - s) * 1e-9 == pytest.approx(sec, abs=1e-4)
    from_spans = {"kind": "serve", "sched": {
        "prime_s": [(e - s) * 1e-9 for s, e in spans.values()]}}
    assert _reader("prime_ms_p90")(from_spans) == pytest.approx(
        _reader("prime_ms_p90")(_ctx(serve)), abs=1.0)


def test_device_labels_carry_no_name_scope(serve):
    """Why there is no `bank_apply_share`: a v5e trace labels a device op
    with its HLO text alone, and the bank's fusions are named for their
    kind (`%fusion.<n>`), so the `bank_apply.` scope reaches no label. The
    kernel's own name does, as its instruction name."""
    ops = serve["devices"][0]["ops"]
    assert not any("bank_apply" in n or "op_name" in n for n, _, _ in ops)
    assert any(n.startswith("%paged_attention.") for n, _, _ in ops)


def test_paged_attention_roofline_from_the_kernel_name(serve):
    c = serve["ctx"]
    kernel = [(s, e) for n, s, e in serve["devices"][0]["ops"]
              if n.startswith("%paged_attention") and " custom-call(" in n
              and _in_window(serve, s, e)]
    assert kernel, "the slice holds paged-attention kernels"
    keys = 0
    for r, P in zip(c["results"], c["P"]):
        for i, t in enumerate(r["stamps"]):
            if i >= 1 and c["w0"] <= t < c["w1"]:
                keys += P + i
    per_key = 36 * 2 * 8 * 128 * 2           # layers, K and V, heads, dh, bf16
    least = keys * per_key / 819e9
    want = 100.0 * least / (sum(e - s for s, e in kernel) * 1e-9)
    got = _reader("paged_attention_roofline")(_ctx(serve))
    assert 0 < got < 100
    assert got == pytest.approx(want, rel=1e-6)


def test_shape_matched_kernel_is_the_named_kernel(serve):
    """`paged_attention_share` (operand shapes) and the name find the same
    ops while the kernel keeps its operands."""
    from bench.metrics._kernels import PAGED_ATTENTION
    named = _reader("paged_attention_roofline").__globals__["KERNEL"]
    by_shape = {i for i, (n, _, _) in enumerate(serve["devices"][0]["ops"])
                if re.search(PAGED_ATTENTION, n)}
    by_name = {i for i, (n, _, _) in enumerate(serve["devices"][0]["ops"])
               if re.search(named, n)}
    assert by_name and by_name == by_shape


def test_readers_read_nothing_without_the_names():
    """On a trace of a program that names nothing (the training slice,
    recorded before the names existed) the new readers return None."""
    fx = json.loads(TRAIN.read_text())
    ctx = {"kind": "serve", "trace": tr.from_json(fx), "w0": 0.0,
           "w1": 1.0, "results": [], "planned": [], "chips": 1,
           "peaks": harness.peaks_for("TPU v5 lite"),
           "config": harness.load_json(harness.ROOT / "bench" / "configs"
                                       / "qwen3-4b.json")}
    for name in ("prime_frac", "paged_attention_roofline"):
        assert _reader(name)(ctx) is None, name


def test_traced_cpu_serving_run_reads_prime_frac():
    """The program's own spans, end to end: a traced serving run at a size
    the CPU holds has the pump's spans in its trace, `prime_frac` reads
    them, and the device readers (no TPU plane here) read nothing."""
    from bench.drivers import serve as serving
    from bench.tests.tiny import tiny_cell
    cell = tiny_cell("qwen3-4b.serve-64tenants")
    res = serving.run(cell, seed=2**33 + 17, seconds=2.0, trace=True,
                      t_start=time.perf_counter(), devices=jax.devices()[:1])
    summary = tr.summarize(res.trace_dir, 1)
    shutil.rmtree(res.trace_dir, ignore_errors=True)
    names = {o.name for o in summary.host}
    assert {"sched.tick", "sched.admit", "sched.prime", "sched.decode",
            "gateway.commands", "gateway.dispatch"} <= names
    ctx = dict(res.context, trace=summary, chips=1,
               peaks=harness.peaks_for("TPU v5 lite"))
    got = harness.read_per_layer(cell, ctx)
    assert 0 < got["prime_frac"]["value"] < 100
    assert "paged_attention_roofline" not in got
