"""The one traffic generator: turns a mix file (``bench/traffic/<name>.json``)
and a seed into an open-loop schedule of requests.

Every seed gets the same work in another order.  Lengths and inter-arrival
gaps are the quantiles of the mix's distributions at (i + 0.5) / n, so a
group of n requests holds one fixed multiset of prompt lengths, of output
lengths and of gaps; the seed only permutes them within the group (prompt
and output lengths separately) and draws the token ids.

A backlog is cut into groups of ``block``.  Open-loop arrivals come in
phases whose gaps are scaled to fill the phase exactly: the pre-roll
(``preroll_s``, n = round(rate * preroll_s)), then the measured window
(``window_s``, n = round(rate * window_s)), whose first request is due at
its open, then groups of ``block`` after it.  So every seed has the same
requests due inside the window, and runs differ by order alone.

Mix keys:
  engine      slots, max_len, chunk: the engine settings the mix is run at
  arrivals    {"kind": "backlog"}: every request due at 0 (the harness keeps
              a slot's worth of them queued, so the backlog never empties)
              {"kind": "poisson", "rate_per_s": x}: exponential gaps, mean 1/x
  prompt_len, output_len
              {"dist": "lognormal", "median", "sigma", "min", "max"} or
              {"dist": "uniform", "min", "max"}; clipped to [min, max]
  block       requests per group of a backlog, and after the window
  preroll_s   seconds of arrivals before the measured window opens
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np


@dataclass
class Planned:
    uid: int
    due_s: float          # seconds after the arrival clock starts
    prompt: list[int]
    max_new: int


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    k = mix["block"]
    check_fits(mix, length_grid(mix["prompt_len"], k),
               length_grid(mix["output_len"], k))
    return mix


def check_fits(mix: dict, p_grid: list[int], o_grid: list[int]) -> None:
    """The engine stops a request at max_len - 1 tokens: a longer one would
    end on capacity, not on its length."""
    hi = max(p_grid) + max(o_grid)
    if hi > mix["engine"]["max_len"] - 1:
        raise ValueError(f"{mix['name']}: longest prompt + longest output = "
                         f"{hi} does not fit max_len - 1 = "
                         f"{mix['engine']['max_len'] - 1}")


def quantiles(dist: dict, k: int) -> list[float]:
    """The distribution's quantiles at (i + 0.5) / k, clipped."""
    us = [(i + 0.5) / k for i in range(k)]
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        vals = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(u))
                for u in us]
    elif dist["dist"] == "uniform":
        vals = [dist["min"] + u * (dist["max"] - dist["min"]) for u in us]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return [min(max(v, dist["min"]), dist["max"]) for v in vals]


def length_grid(dist: dict, k: int) -> list[int]:
    return [int(round(v)) for v in quantiles(dist, k)]


def gap_grid(k: int, span: float) -> list[float]:
    """Exponential quantiles, scaled so that they sum to exactly ``span``."""
    gaps = [-math.log(1.0 - (i + 0.5) / k) for i in range(k)]
    scale = span / sum(gaps)
    return [g * scale for g in gaps]


def phases(mix: dict, window_s: float, rate: float | None):
    """(requests, seconds) of each group, without end: ``seconds`` is None
    for a backlog, whose requests are all due at 0."""
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        while True:
            yield mix["block"], None
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrivals {arr['kind']!r}")
    rate = rate or arr["rate_per_s"]
    for span in (mix["preroll_s"], window_s):
        yield max(1, round(rate * span)), span
    while True:
        yield mix["block"], mix["block"] / rate


def schedule(mix: dict, seed: int, vocab: int, window_s: float, *,
             uid0: int = 0, rate: float | None = None):
    """Yield ``Planned`` requests in due order, without end, for a window
    of ``window_s`` seconds after the pre-roll.  ``rate`` overrides the
    mix's rate (the knee sweep)."""
    rng = np.random.default_rng(seed)
    t, uid = 0.0, uid0
    for k, span in phases(mix, window_s, rate):
        p_grid = length_grid(mix["prompt_len"], k)
        o_grid = length_grid(mix["output_len"], k)
        check_fits(mix, p_grid, o_grid)
        pp, po = rng.permutation(k), rng.permutation(k)
        if span is not None:
            g_grid = gap_grid(k, span)
            pg = rng.permutation(k)
        t0 = t
        for i in range(k):
            prompt = rng.integers(0, vocab, size=p_grid[pp[i]]).tolist()
            yield Planned(uid, t, prompt, o_grid[po[i]])
            uid += 1
            if span is not None:
                t += g_grid[pg[i]]
        if span is not None:
            t = t0 + span           # no rounding drift between phases
