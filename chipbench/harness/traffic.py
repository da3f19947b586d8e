"""The one traffic generator: a mix is a JSON file of parameters
(``chipbench/traffic/<mix>.json``), read here.

Lengths are lognormal (median, sigma), clipped to [min, max] and rounded
up to a grid.  They are drawn by stratified quantiles, so every seed
gets the same multiset of lengths in another order: the seed changes
which request is long and when it comes, not how much work a run holds.

Arrivals: ``rate`` requests per second over the window, a fixed count.
A ``burst_share`` of them arrive in bursts every ``burst_every_s``
seconds, each spread uniformly over ``burst_spread_s``; the rest arrive
with exponential gaps (a Poisson process), the gaps too a stratified set
shuffled by the seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Planned:
    rid: int
    prompt_len: int
    out_len: int
    due: Optional[float] = None   # seconds after the window opens


def _quantile_lengths(dist: dict, n: int) -> List[int]:
    lo, hi = dist["min"], dist["max"]
    grid = dist.get("grid", 1)
    if dist.get("sigma", 0) == 0 or lo == hi:
        vals = [dist.get("median", lo)] * n
    else:
        nd = NormalDist()
        vals = [math.exp(math.log(dist["median"])
                         + dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
                for i in range(n)]
    out = []
    for v in vals:
        v = min(max(v, lo), hi)
        out.append(min(int(math.ceil(v / grid) * grid), hi))
    return out


def lengths(dist: dict, n: int, rng: np.random.Generator) -> List[int]:
    """``n`` lengths: the stratified multiset, shuffled by ``rng``."""
    vals = _quantile_lengths(dist, n)
    return [vals[i] for i in rng.permutation(n)]


def open_schedule(mix: dict, seconds: float, seed: int) -> List[Planned]:
    """Requests due in a window of ``seconds``, in order of due time."""
    rng = np.random.default_rng([seed, 1])
    arr = mix["arrivals"]
    n = int(round(arr["rate"] * seconds))
    n_burst = int(round(arr.get("burst_share", 0.0) * n))
    m = n - n_burst
    # exponential gaps by stratified quantiles, shuffled: every seed has
    # the same gaps, in another order
    gaps = np.array([-math.log(1.0 - (i + 0.5) / m) for i in range(m)])
    gaps = gaps[rng.permutation(m)]
    times = list(np.cumsum(gaps) * seconds / (gaps.sum() + gaps.mean())) \
        if m else []
    if n_burst:
        every, spread = arr["burst_every_s"], arr["burst_spread_s"]
        n_bursts = max(int(seconds // every), 1)
        for k in range(n_bursts):
            start = (k + 0.5) * every - spread / 2
            size = n_burst // n_bursts + (k < n_burst % n_bursts)
            times += list(start + rng.uniform(0.0, spread, size))
    times.sort()
    plens = lengths(mix["prompt"], n, rng)
    olens = lengths(mix["output"], n, rng)
    return [Planned(i, plens[i], olens[i], float(t))
            for i, t in enumerate(times)]


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """Request ``rid``'s prompt, (1, length) int32, ids in [2, vocab)."""
    rng = np.random.default_rng([seed, 3, rid])
    return rng.integers(2, vocab, (1, length), dtype=np.int32)


def bounds(dist: dict) -> tuple:
    """(smallest, largest) length the distribution can give."""
    vals = _quantile_lengths({**dist, "sigma": 0, "median": dist["min"]}, 1) \
        + _quantile_lengths({**dist, "sigma": 0, "median": dist["max"]}, 1)
    return min(vals), max(vals)


def grid_values(dist: dict) -> List[int]:
    """Every length the distribution can give, in order."""
    lo, hi = bounds(dist)
    g = dist.get("grid", 1)
    return list(range(lo, hi + 1, g)) if lo != hi else [lo]
