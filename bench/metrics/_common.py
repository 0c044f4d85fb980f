"""Shared arithmetic of the per-layer readers (not a metric itself)."""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from work import counts  # noqa: E402


def decode_tokens(run):
    """Contexts of the tokens the traced ticks' decode steps produced."""
    return [c for t in run["trace"]["harness_ticks"]
            for c in t["decode_contexts"]]


def decode_calls(run) -> int:
    return sum(1 for t in run["trace"]["harness_ticks"]
               if t["decode_contexts"])


def roofline(flops: float, nbytes: float, seconds: float, peak: dict):
    """Least time the chip could take over the time taken, in percent;
    ``None`` when nothing ran."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least = max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


__all__ = ["counts", "decode_tokens", "decode_calls", "roofline"]
