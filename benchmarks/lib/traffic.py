"""The one general traffic generator.

A traffic file fixes the offered work: the multiset of (prompt length,
output length) pairs, their order and every arrival time come from the
file's own `trace_seed` by stratified quantiles of the stated
distributions (the i-th of N values is the (i+1/2)/N quantile, then one
fixed permutation). `--seed` never reaches this module: it only makes
token ids and weights (see `token_ids`).
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Any, Dict, List, NamedTuple

import numpy as np


class Request(NamedTuple):
    index: int
    due_s: float        # arrival time at unit rate scale, 0.0 in a closed loop
    prompt_len: int
    output_len: int


def _quantile(dist: Dict[str, Any], q: float) -> float:
    kind = dist["dist"]
    if kind == "fixed":
        return float(dist["value"])
    if kind == "lognormal":
        z = NormalDist().inv_cdf(q)
        return float(dist["median"]) * math.exp(float(dist["sigma"]) * z)
    if kind == "loguniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        return lo * (hi / lo) ** q
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: Dict[str, Any], n: int, rng: random.Random
               ) -> List[int]:
    """n whole numbers on the quantile grid of `dist`, clipped to its
    `min`/`max`, in one fixed random order."""
    vals = []
    for i in range(n):
        v = _quantile(dist, (i + 0.5) / n)
        v = max(float(dist.get("min", v)), min(float(dist.get("max", v)), v))
        vals.append(int(round(v)))
    rng.shuffle(vals)
    return vals


def arrival_times(n: int, rate: float, rng: random.Random) -> List[float]:
    """Poisson-like arrivals at `rate` a second: the gaps are the
    exponential distribution's quantile grid in one fixed order, so
    their sum, and the rate over any long stretch, are exact."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng.shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(t)
    return out


def make_trace(traffic: Dict[str, Any], rate: float = 0.0) -> List[Request]:
    """The cell's whole offered work. `rate` overrides the file's (the
    sweep's ladder); a closed loop has no arrival times."""
    n = int(traffic["n_requests"])
    rng = random.Random(int(traffic["trace_seed"]))
    prompts = stratified(traffic["prompt_len"], n, rng)
    outputs = stratified(traffic["output_len"], n, rng)
    limit = int(traffic["max_total_len"])
    rate = rate or float(traffic.get("rate_req_s", 0.0))
    due = arrival_times(n, rate, rng) if rate > 0 else [0.0] * n
    out = []
    for i in range(n):
        o = max(1, min(outputs[i], limit - prompts[i]))
        out.append(Request(i, due[i], prompts[i], o))
    return out


def token_ids(seed: int, trace: List[Request], vocab_size: int
              ) -> List[List[int]]:
    """Every prompt's token ids, from `--seed` alone (any whole number:
    numpy folds it into its generator's state)."""
    rng = np.random.default_rng([int(seed), 0x70656E])
    return [rng.integers(0, vocab_size, size=r.prompt_len).tolist()
            for r in trace]
