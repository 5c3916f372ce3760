"""Seeded request schedules from a traffic file (``bench/traffic/*.json``).

One generator for every serving mix.  The file gives the arrival process
and the length distributions; the seed gives the order and the tokens.
Every seed gets the same set of lengths and inter-arrival gaps — the
stratified quantiles of the stated distributions — in its own order, so
that two seeds differ in order and content, not in how much work they
bring.  (The idea follows ``benchmarks/serving_bench.build_workload``:
Poisson arrivals per client with lengths from a fixed set.)

* ``"process": "poisson"`` — open loop: request ``i`` is due at a fixed
  time after the window opens, whatever the server does.
* ``"process": "closed"`` — closed loop: ``clients`` callers, each with
  its own queue of requests; a caller's next request is due the moment its
  previous reply reaches the host (zero think time), the first one at an
  evenly spread offset inside ``ramp_s``.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    n_out: int
    due: Optional[float] = None    # seconds after the window opens (open loop)
    client: int = 0
    submitted: float = math.nan    # host clock at submission
    finished: float = math.nan     # host clock when its tokens reached the host
    due_at: float = math.nan       # absolute due time on the host clock
    handle: object = None          # the engine's request
    tokens: object = None          # served tokens, once finished


def quantile_set(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as integers."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
    vals = np.rint(dist["median"] * np.exp(dist["sigma"] * z)).astype(np.int64)
    return np.clip(vals, dist["min"], dist["max"])


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the exponential gap of a Poisson
    process at ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def request_count(traffic: dict, seconds: float) -> int:
    arr = traffic["arrivals"]
    if arr["process"] == "poisson":
        # The window and the drain both see requests; a tenth more covers
        # the window whatever the order of the gaps.
        return int(math.ceil(arr["rate_per_s"] * seconds * 1.1)) + 8
    return arr["clients"] * arr["requests_per_client"]


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """The run's requests, in submission order for the open loop and in
    client order (client-major) for the closed loop."""
    rng = np.random.default_rng(seed)
    n = request_count(traffic, seconds)
    plen = rng.permutation(quantile_set(traffic["prompt_len"], n))
    olen = rng.permutation(quantile_set(traffic["output_len"], n))
    arr = traffic["arrivals"]
    reqs = [Req(rid=i, prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                n_out=int(olen[i])) for i in range(n)]
    if arr["process"] == "poisson":
        due = np.cumsum(rng.permutation(exp_gaps(arr["rate_per_s"], n)))
        for r, t in zip(reqs, due):
            r.due = float(t)
    elif arr["process"] == "closed":
        per = arr["requests_per_client"]
        for r in reqs:
            r.client = r.rid // per
        for c in range(arr["clients"]):
            reqs[c * per].due = arr["ramp_s"] * c / arr["clients"]
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return reqs


def lateness(reqs: List[Req], window_start: float, window_end: float) -> np.ndarray:
    """Seconds each submission inside the window went out after it was
    due: how far the load generator fell behind its own schedule."""
    return np.array([r.submitted - r.due_at for r in reqs
                     if window_start <= r.due_at < window_end
                     and not math.isnan(r.submitted)])
