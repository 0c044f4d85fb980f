"""The comparison that decides ``correct``, against the plain reference.

Each compared number has a limit of its own, kept in the configuration
file (``limits``) with the readings it was set from in ``PERF.md``.  A
run is correct when every number is at or under its limit.

A sample drawn from the seed of the requests the window finished, the
one with the most served tokens always in it, is run once through the
reference over each prompt with its served tokens (teacher forced).  At
every served position it reads how far the served token's logit lies
below the reference's best, in standard deviations of that logit row,
and how many tokens the reference ranks above the served one.  Of the
widest gap, the mean gap, the share of tokens more than one standard
deviation below and the worst rank, the configuration's ``limits`` name
the ones compared (``GAP_STATS``); the others are printed beside them.
"""

from __future__ import annotations

import sys
from typing import List

import jax.numpy as jnp
import numpy as np


def compared(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": float(value), "limit": float(limit)}


def is_correct(items: List[dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in items)


def report(items: List[dict]) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    for c in items:
        print(f"compared {c['name']} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)


def _limit(cell, name: str) -> float:
    return float(cell.config["limits"][name])


# --- serving ---------------------------------------------------------------

def sample_requests(finished, seed: int, k: int):
    """The request with the most served tokens, and ``k`` others drawn
    from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.generated), -r.rid))
    rest = sorted((r for r in finished if r is not longest),
                  key=lambda r: r.rid)
    rng = np.random.default_rng([seed, 0x5E1EC7])
    pick = rng.choice(len(rest), size=min(k, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(ref, params, reqs, sizes: dict, max_prompt: int,
                max_new: int, quant=None) -> dict:
    """Per served token: ``gap``, the reference's best logit minus the
    served token's in row standard deviations, and ``rank``, how many
    tokens the reference puts above it (with ``quant``: of the token the
    lowered-precision reference puts first)."""
    mats = ref.transform_mats(sizes)
    length = max_prompt + max_new
    out = {"gap": [], "rank": []}
    for r in reqs:
        gen = [int(t) for t in r.generated]
        seq = list(r.prompt) + gen[:-1]
        tokens = np.zeros((length,), np.int32)
        tokens[:len(seq)] = seq
        n, p = len(gen), len(r.prompt)
        rows = np.full((max_new,), p - 1 + n - 1, np.int32)
        rows[:n] = np.arange(p - 1, p - 1 + n)
        targets = np.zeros((max_new,), np.int32)
        targets[:n] = gen
        g = ref.token_gaps(params, mats, jnp.asarray(tokens),
                           jnp.asarray(rows), jnp.asarray(targets), sizes,
                           quant)
        for k in out:
            out[k].append(np.asarray(g[k])[:n])
    return {k: np.concatenate(v) if v else np.zeros((0,))
            for k, v in out.items()}


#: the served-token statistics a configuration may hold to a limit
GAP_STATS = ("served_gap_std", "served_gap_mean_std",
             "served_share_over_1std", "served_rank_max")


def gap_stats(got: dict) -> dict:
    """Widest gap, mean gap, share of tokens more than one row standard
    deviation below the reference's best, and the worst rank (inf when
    nothing was served)."""
    gaps, ranks = got["gap"], got["rank"]
    if not gaps.size:
        return {k: float("inf") for k in GAP_STATS}
    return {"served_gap_std": float(gaps.max()),
            "served_gap_mean_std": float(gaps.mean()),
            "served_share_over_1std": float(np.mean(gaps > 1.0)),
            "served_rank_max": float(ranks.max())}


def items(cell, got: dict) -> List[dict]:
    """The statistics the configuration holds to a limit, beside it."""
    return [compared(k, got[k], _limit(cell, k)) for k in GAP_STATS
            if k in cell.config["limits"]]


def serve(cell, seed: int, params, finished, shape: dict,
          sizes: dict) -> List[dict]:
    reqs = sample_requests(finished, seed, shape["check_sample"])
    ref = cell.reference()
    got = gap_stats(served_gaps(ref, params, reqs, sizes,
                                shape["max_prompt_len"],
                                shape["max_new_tokens"]))
    n = sum(len(r.generated) for r in reqs)
    print(f"[check] {n} served tokens of {len(reqs)} requests "
          f"(of {len(finished)} finished in the window) against the "
          f"float32 reference: " + ", ".join(
              f"{k} {v!r}" for k, v in got.items()),
          file=sys.stderr, flush=True)
    return items(cell, got)
