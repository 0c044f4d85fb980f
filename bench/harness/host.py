"""The engine's host phases, from the profiler capture of a traced run.

The engine names every piece of its tick with a ``TraceAnnotation``
(``repro/obs/prof.py``): ``engine.tick`` holds ``engine.expire``,
``engine.admit`` (one ``prefill`` per admission, split into
``prefill.inputs``, ``prefill.launch`` and ``prefill.sample``),
``engine.map``, ``engine.rng``, ``decode`` (``decode.inputs``,
``decode.launch``, ``decode.wait``), ``engine.commit`` and
``engine.pressure``.  ``trace.py`` keeps only the harness's spans and
the program classes; this reduces the same ``.xplane.pb`` for the rest:

* ``ticks``: one record per ``engine.tick`` inside the window (the first
  to the last harness span): ``dur`` in seconds, ``total`` (seconds by
  phase name, summed over the tick) and ``self`` (the same less the time
  of the phases nested in each), ``decode`` (the tick ran a decode),
  ``prefills`` (how many it ran) and ``spans`` (how many it opened).
  The ``self`` times of a tick add up to its ``dur``;
  ``self["engine.tick"]`` is host time in no phase;
* ``idle`` and ``idle_longest``: the device's idle gaps named as
  ``trace.py`` names them, by the innermost span around each gap's
  midpoint, with the engine's phases among the names.

A capture of a program that names no phase gives no ticks, and the
readers of these records then report nothing.
"""

from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path
from typing import Optional

from harness import trace

#: the engine's phase spans (``repro/obs/prof.py``), besides the program
#: classes ``prefill`` and ``decode``
ENGINE = ("engine.tick", "engine.expire", "engine.admit", "engine.map",
          "engine.rng", "engine.commit", "engine.pressure",
          "decode.inputs", "decode.launch", "decode.wait",
          "prefill.inputs", "prefill.launch", "prefill.sample",
          "draft", "verify", "verify.inputs", "verify.launch",
          "verify.wait")
#: every span a tick holds
PHASES = ENGINE + ("prefill", "decode")
#: every span an idle gap may be named by
NAMES = trace.HARNESS + trace.CLASSES + ENGINE


class Spans:
    """Host spans ``(name, start, end)``, searchable by time."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0.0)

    def around(self, t: float, names) -> Optional[tuple]:
        """The innermost span named in ``names`` that holds ``t``: of
        those that do, the latest to start (then the first to end).
        Every span that starts within the longest span's duration before
        ``t`` is looked at, however many there are."""
        lo = bisect.bisect_left(self.starts, t - self.longest)
        hi = bisect.bisect_right(self.starts, t)
        held = [x for x in self.spans[lo:hi]
                if x[0] in names and x[1] <= t <= x[2]]
        return max(held, key=lambda x: (x[1], -x[2]), default=None)


def _tick(spans) -> dict:
    """The record of one ``engine.tick`` from the phase spans
    ``(name, start, dur)`` it holds, itself among them."""
    total, own = collections.Counter(), collections.Counter()
    for name, _s, d, self_s in trace._self_times(spans):
        total[name] += d
        own[name] += self_s
    return {"dur": total["engine.tick"], "total": dict(total),
            "self": dict(own), "decode": "decode" in total,
            "prefills": sum(1 for n, _, _ in spans if n == "prefill"),
            "spans": len(spans)}


def reduce_profile(profile) -> dict:
    """``ticks``, ``idle`` and ``idle_longest`` (module docstring) of a
    ``jax.profiler.ProfileData``."""
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in NAMES:
                        spans.append((ev.name, ev.start_ns / 1e9,
                                      (ev.start_ns + ev.duration_ns) / 1e9))
        elif re.match(r"^/device:TPU:\d+$", plane.name):
            devices.append({line.name: list(line.events)
                            for line in plane.lines})
    bounds = ([x for x in spans if x[0] in trace.HARNESS]
              or [x for x in spans if x[0] == "engine.tick"])
    if not bounds:
        return {"ticks": [], "idle": {}, "idle_longest": []}
    lo = min(s for _, s, _ in bounds)
    hi = max(e for _, _, e in bounds)
    host = Spans(spans)

    ticks = []
    phases = [x for x in host.spans if x[0] in PHASES]
    starts = [s for _, s, _ in phases]
    for name, s, e in phases:
        if name != "engine.tick" or s < lo or e > hi:
            continue
        i, j = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        ticks.append(_tick([(n, a, b - a) for n, a, b in phases[i:j]
                            if b <= e]))

    gaps, longest = collections.Counter(), []
    for lines in devices:
        mods = sorted((ev.start_ns / 1e9,
                       (ev.start_ns + ev.duration_ns) / 1e9)
                      for ev in lines.get("XLA Modules", []))
        prev = lo
        for s, e in mods + [(hi, hi)]:
            s_c = max(s, lo)
            if s_c > prev:
                span = host.around((prev + s_c) / 2, NAMES)
                name = span[0] if span else "waiting"
                if name == "tick":
                    name = "tick/host"
                gaps[name] += s_c - prev
                longest.append((name, s_c - prev))
            prev = max(prev, min(e, hi))
    return {"ticks": ticks, "idle": dict(gaps),
            "idle_longest": sorted(longest, key=lambda g: -g[1])[:10]}


def reduce_file(path) -> dict:
    """The reduction of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))


def load(directory: Path = trace.DIR) -> Optional[dict]:
    """The reduction of the newest capture under ``directory`` (where a
    traced run's ``Tracer`` writes it); ``None`` when there is none."""
    files = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    return reduce_file(files[-1]) if files else None
