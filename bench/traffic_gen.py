"""The one generator of serving traffic: reads a mix's parameters (a
bench/traffic/*.json file of kind `serve`) and draws the requests of a run
from the seed.

A run has two parts, the lead-in and the window, and each part gets
N = round(rate x its length) requests: gaps between arrivals the
exponential law's quantiles at (i + 0.5) / N (an open Poisson loop at
`rate`), scaled so that the N arrivals fall inside the part; prompt and
output lengths the lognormal's quantiles at the same points, clipped to the
mix's bounds; tenants the Zipf law's quantiles over the bank. One fixed
permutation of each list, the same for every seed, makes the schedule: when
each request arrives and how long its prompt and output are. The order
matters to the tail of the gaps between tokens, which turns on which
prompts are primed while which streams run, so the seed does not choose it:
the seed permutes the tenants over the requests and draws the prompt
tokens. Every seed offers the same work, to other tenants and with other
tokens.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from bench import harness


@dataclass
class Planned:
    due: float               # seconds after the schedule's start
    tenant: int
    prompt: List[int]
    max_tokens: int


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Standard normal quantiles (Acklam's rational approximation, relative
    error under 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                * r + b[4]) * r + 1)
    return out


def grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec: Dict, n: int) -> np.ndarray:
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(grid(n)))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_tenants(n_tenants: int, a: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n_tenants + 1) ** a
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, grid(n)), n_tenants - 1)


def poisson_gaps(n: int) -> np.ndarray:
    """Gaps between n arrivals of a unit-rate Poisson process: the
    exponential law's quantiles at (i + 0.5) / n."""
    return -np.log1p(-grid(n))


def plan(mix: Dict, seconds: float, seed: int, vocab: int,
         avoid_token: int = -1) -> List[Planned]:
    """The requests of one run, by due time: a lead-in of `lead_in_s`
    seconds, then the window of `seconds`, at the mix's fixed rate."""
    rate, lead = float(mix["rate"]), float(mix["lead_in_s"])
    rng = harness.np_rng(seed, 11)
    fixed = harness.np_rng(0, 11)          # the schedule, for every seed
    reqs = []
    for lo, hi in ((0.0, lead), (lead, lead + seconds)):
        n = max(1, round(rate * (hi - lo)))
        gaps = fixed.permutation(poisson_gaps(n))
        # the part holds the n gaps and one more of the mean's length
        due = lo + (hi - lo) * np.cumsum(gaps) / (gaps.sum() + gaps.mean())
        prompts = fixed.permutation(lognormal_sizes(mix["prompt_tokens"], n))
        outs = fixed.permutation(lognormal_sizes(mix["output_tokens"], n))
        tenants = rng.permutation(
            zipf_tenants(mix["tenants"], mix["zipf_a"], n))
        for i in range(n):
            toks = rng.integers(1, vocab, int(prompts[i]))
            toks[toks == avoid_token] = 1
            reqs.append(Planned(float(due[i]), int(tenants[i]),
                                [int(t) for t in toks], int(outs[i])))
    return reqs
