"""The one generator every traffic mix goes through.

A mix is a data file (``bench/traffic/<name>.json``); this module reads
its parameters and makes the inputs from ``--seed``.  A mix's ``kind``
names the loop that drives it (``serve``: open-loop arrivals,
``harness/serve_loop.py``).

Serving mixes draw lengths and gaps by stratified quantiles: a segment of
``n`` requests takes the quantiles at ``(i + 0.5) / n`` of each
distribution, and the seed only shuffles them and draws the token ids.
Every seed then offers the same set of sizes and arrivals in another
order, so the work in a window does not depend on the seed.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Arrival:
    """One request of the schedule: when it is due and what it asks."""

    rid: int
    due: float                 # seconds from the start of warm-up traffic
    prompt: List[int]
    max_new_tokens: int
    in_window: bool


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified lengths of a ``lognormal`` spec, shuffled by
    ``rng``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([_NORMAL.inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


def gaps(rate: float, n: int, span: float,
         rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps at ``rate``,
    shuffled and scaled so that they add up to ``span`` seconds."""
    g = -np.log1p(-_quantiles(n)) / rate
    g = rng.permutation(g)
    return g * (span / g.sum())


def serve_schedule(mix: dict, seed: int, window_s: float,
                   vocab: int) -> List[Arrival]:
    """Warm-up arrivals over ``mix["warmup_s"]``, then the window's over
    ``window_s``; both at ``mix["rate_rps"]``.  Every request is greedy
    with no end-of-sequence token and no prefix shared with another."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    rng = np.random.default_rng(seed)
    rate = float(mix["rate_rps"])
    out: List[Arrival] = []
    t0 = 0.0
    for span, in_window in ((float(mix["warmup_s"]), False),
                            (float(window_s), True)):
        n = max(1, int(round(rate * span)))
        p = lengths(mix["prompt"], n, rng)
        o = lengths(mix["output"], n, rng)
        g = gaps(rate, n, span, rng)
        # each arrival sits in the middle of its gap, inside the segment
        due = t0 + np.cumsum(g) - g / 2
        for i in range(n):
            out.append(Arrival(
                rid=len(out), due=float(due[i]),
                prompt=rng.integers(0, vocab, int(p[i])).tolist(),
                max_new_tokens=int(o[i]), in_window=in_window))
        t0 += span
    return out
