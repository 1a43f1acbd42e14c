"""The serving traffic generator: every seed draws the same schedule of
arrivals and sizes, with its own tenants and tokens."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import traffic_gen

MIX = json.loads((Path(__file__).parents[1] / "traffic"
                  / "serve-64tenants.json").read_text())


def test_same_work_for_every_seed():
    a = traffic_gen.plan(MIX, 30.0, 1, 1000)
    b = traffic_gen.plan(MIX, 30.0, 2**40 + 3, 1000)
    assert len(a) == len(b)
    for key in ("max_tokens", "tenant"):
        assert sorted(getattr(p, key) for p in a) == \
            sorted(getattr(p, key) for p in b)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert [p.prompt for p in a] != [p.prompt for p in b]


def test_sizes_follow_the_mix():
    plan = traffic_gen.plan(MIX, 30.0, 5, 1000, avoid_token=7)
    lens = np.array([len(p.prompt) for p in plan])
    outs = np.array([p.max_tokens for p in plan])
    pt, ot = MIX["prompt_tokens"], MIX["output_tokens"]
    assert lens.min() >= pt["min"] and lens.max() <= pt["max"]
    assert outs.min() >= ot["min"] and outs.max() <= ot["max"]
    assert abs(np.median(lens) - pt["median"]) < 0.1 * pt["median"]
    assert all(7 not in p.prompt for p in plan)
    dues = np.array([p.due for p in plan])
    lead = MIX["lead_in_s"]
    assert np.all(np.diff(dues) >= 0) and 0 <= dues[0] and dues[-1] < lead + 30
    in_window = ((dues >= lead) & (dues < lead + 30)).sum()
    assert in_window == round(MIX["rate"] * 30)
    assert len(plan) == in_window + round(MIX["rate"] * lead)


def test_the_window_gets_the_same_sizes_for_every_seed():
    lead = MIX["lead_in_s"]

    def window(seed):
        ps = [p for p in traffic_gen.plan(MIX, 30.0, seed, 1000)
              if p.due >= lead]
        return (sorted(len(p.prompt) for p in ps),
                sorted(p.max_tokens for p in ps), sorted(p.tenant for p in ps))
    assert window(3) == window(2**40 + 9)


def test_every_seed_gets_the_same_schedule():
    def schedule(seed):
        return [(p.due, len(p.prompt), p.max_tokens)
                for p in traffic_gen.plan(MIX, 30.0, seed, 1000)]
    assert schedule(4) == schedule(2**40 + 11)
    lead = MIX["lead_in_s"]
    gaps = np.diff([lead] + [d for d, _, _ in schedule(4) if d >= lead])
    assert np.allclose(np.sort(gaps) / gaps.mean(),
                       traffic_gen.poisson_gaps(len(gaps))
                       / traffic_gen.poisson_gaps(len(gaps)).mean())


def test_zipf_tenants_favour_the_first():
    t = traffic_gen.zipf_tenants(64, 1.0, 1000)
    counts = np.bincount(t, minlength=64)
    assert counts[0] > counts[1] > counts[10] and counts[0] > 150


def test_normal_quantiles():
    from math import erf, sqrt
    p = np.array([1e-4, 0.01, 0.3, 0.5, 0.9, 0.999])
    x = traffic_gen._norm_ppf(p)
    cdf = np.array([0.5 * (1 + erf(v / sqrt(2))) for v in x])
    assert np.allclose(cdf, p, rtol=1e-6, atol=1e-9)
