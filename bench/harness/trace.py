"""Capture a profiler trace in the middle of the window, and reduce it.

Capture (``--trace 1``): ``jax.profiler`` runs for the mix's ``trace_s``
seconds centred in the window, with the Python tracer off.  The harness
wraps its own calls in ``TraceAnnotation`` spans (``submit``, ``tick``,
``bookkeeping``; ``feed``, ``step``) and the engine adds its ``prefill``
and ``decode`` scopes (``obs/prof.py``).

Reduction, from the ``.xplane.pb`` alone:

* the window is the first to the last harness span in the trace;
* busy time is the union of the device's program executions (the
  ``XLA Modules`` line of each ``/device:TPU:n`` plane) inside it;
* each program execution takes the class of the engine or harness span
  around its midpoint (``prefill``, ``decode``, ``step``; else ``other``),
  and a call's device time is the sum of the executions in its span (the
  step program with the small programs the call dispatches beside it);
* each operation (``XLA Ops``) counts its self time, its duration less
  that of the operations nested in it, in the class of the program it ran
  in, under its instruction's name without the ``.N`` suffix.  The trace
  carries no JAX name stack, so kernels are told apart by that name: a
  ``tpu_custom_call`` takes the class that a file under ``bench/kernels``
  gives its name (``sell``, ``paged_attn``; a later kernel adds a file),
  and ``unknown`` where none does.  The kernel readers refuse a trace
  with an ``unknown`` kernel in it, so that a new kernel's time never
  lands in another kernel's share;
* an idle gap between program executions is named by the innermost
  harness or engine span around its midpoint (``tick/host`` is a tick's
  host work outside prefill and decode).
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import json
import re
import shutil
from pathlib import Path
from typing import List, Optional

import jax

#: harness spans (the window) and the engine's scopes (program classes)
HARNESS = ("submit", "tick", "bookkeeping", "feed", "step")
CLASSES = ("prefill", "decode", "step")
BENCH = Path(__file__).resolve().parents[1]
DIR = BENCH.parent / ".cache" / "bench_trace"


def kernel_table(root: Path = BENCH) -> list:
    """``(pattern, class)`` for every kernel file under ``root/kernels``."""
    table = []
    for f in sorted((root / "kernels").glob("*.json")):
        spec = json.loads(f.read_text())
        table += [(re.compile(m), spec["class"]) for m in spec["match"]]
    return table


def kernel_class(kind: str, table: list) -> str:
    for pattern, cls in table:
        if pattern.search(kind):
            return cls
    return "unknown"


class Tracer:
    """Starts and stops the profiler inside the window and records the
    harness's spans.  Disabled, every span is a shared no-op."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self.done = False
        self.ticks: List[dict] = []    # harness records of traced ticks

    def window(self, now: float, start: float, stop: float) -> None:
        if not self.enabled or self.done:
            return
        if not self.active and now >= start:
            shutil.rmtree(DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(DIR), profiler_options=opts)
            self.active = True
        elif self.active and now >= stop:
            self._stop()

    def _stop(self) -> None:
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def finish(self, reduce: bool = True) -> Optional[dict]:
        """Reduce the trace; ``None`` when the run was not traced.  With
        ``reduce`` off (a rehearsal on a backend with no TPU plane) only
        the harness's records come back."""
        if self.active:
            self._stop()
        if not self.done:
            return None
        if not reduce:
            return {"harness_ticks": self.ticks}
        files = sorted(DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no trace written under {DIR}")
        from jax.profiler import ProfileData
        out = reduce_profile(ProfileData.from_file(str(files[-1])))
        out["harness_ticks"] = self.ticks
        return out


def _union(intervals, lo: float, hi: float) -> float:
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def _kind(name: str) -> str:
    m = re.match(r"%?([\w\-]+?)(\.\d+)?\s*=", name)
    return m.group(1) if m else name.split("(")[0][:60]


def _self_times(ops):
    """``(name, start, dur, self)`` with nested operations' time taken
    out of their parents'.  Nested means wholly inside: neighbours whose
    stamps overlap by a few nanoseconds stay siblings."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out, stack = [], []
    for name, s, d in ops:
        while stack and s + d > stack[-1][1] + stack[-1][2]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= d
        stack.append([name, s, d, d])
    out.extend(tuple(x) for x in reversed(stack))
    return out


class _Spans:
    """Host spans by name, searchable by time."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.spans]

    def around(self, t: float, names) -> Optional[tuple]:
        """The innermost span ``(name, start, end)`` named in ``names``
        that holds ``t``."""
        best = None
        i = bisect.bisect_right(self.starts, t)
        for name, s, e in self.spans[max(0, i - 64):i]:
            if s <= t <= e and name in names:
                if best is None or s >= best[1]:
                    best = (name, s, e)
        return best


def reduce_profile(profile, table: Optional[list] = None) -> dict:
    table = kernel_table() if table is None else table
    spans = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HARNESS or ev.name in CLASSES:
                        spans.append((ev.name, ev.start_ns / 1e9,
                                      (ev.start_ns + ev.duration_ns) / 1e9))
        elif re.match(r"^/device:TPU:\d+$", plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            devices.append(lines)
    harness = [s for s in spans if s[0] in HARNESS]
    if not harness or not devices:
        raise ValueError("the trace holds no harness span or no TPU plane")
    lo = min(s for _, s, _ in harness)
    hi = max(e for _, _, e in harness)
    host = _Spans(spans)
    window = hi - lo

    busy, longest = [], []
    programs = collections.defaultdict(list)
    ops = collections.defaultdict(collections.Counter)
    gaps = collections.Counter()
    for lines in devices:
        mods = [(ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9)
                for ev in lines.get("XLA Modules", [])]
        busy.append(_union(mods, lo, hi))
        cls_of, calls = [], collections.defaultdict(float)
        for s, e in sorted(mods):
            span = host.around((s + e) / 2, CLASSES)
            cls_of.append((s, e, span[0] if span else "other"))
            if span and lo <= span[1] and span[2] <= hi:
                calls[span] += e - s
        for (cls, _, _), dev in sorted(calls.items(), key=lambda c: c[0][1]):
            programs[cls].append(dev)
        starts = [s for s, _, _ in cls_of]
        raw = [(ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9)
               for ev in lines.get("XLA Ops", [])]
        for name, s, _d, own in _self_times(raw):
            if not lo <= s <= hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            cls = cls_of[i][2] if i >= 0 and s <= cls_of[i][1] else "other"
            kind = _kind(name)
            if "tpu_custom_call" in name:
                kind = kernel_class(kind, table) + ":" + kind
            ops[cls][kind] += own
        ends = sorted(cls_of)
        prev = lo
        for s, e, _ in ends + [(hi, hi, None)]:
            s_c = max(s, lo)
            if s_c > prev:
                mid = (prev + s_c) / 2
                span = host.around(mid, HARNESS + CLASSES)
                name = span[0] if span else "waiting"
                if name == "tick":
                    name = "tick/host"
                gaps[name] += s_c - prev
                longest.append((name, s_c - prev))
            prev = max(prev, min(e, hi))
    n = len(devices)
    return {"window_s": window, "busy_s": sum(busy) / n,
            "programs": {k: v for k, v in programs.items()},
            "ops": {k: dict(v) for k, v in ops.items()},
            "idle": dict(gaps),
            "idle_longest": sorted(longest, key=lambda g: -g[1])[:10],
            "chips": n}


def breakdown(red: dict) -> dict:
    """The ten device operations that took most time (by program class
    and instruction), and the ten longest idle gaps, each named by what
    the host was doing."""
    total = collections.Counter()
    for cls, kinds in red["ops"].items():
        for kind, s in kinds.items():
            total[f"{cls}:{kind}"] += s
    return {"device_ops": [[k, v] for k, v in total.most_common(10)],
            "idle_gaps": [[k, v] for k, v in red["idle_longest"]]}


def unknown_kernels(red: dict) -> dict:
    """Self time of each kernel that no file under ``bench/kernels``
    names, by program class and name."""
    return {f"{cls}:{k}": s for cls, kinds in red["ops"].items()
            for k, s in kinds.items() if k.startswith("unknown:")}


def kernel_seconds(red: dict, cls: str, kernel: str) -> float:
    """Self time of the ``kernel`` class's kernels (``"sell"``,
    ``"paged_attn"``) in the programs of class ``cls``.  Raises when the
    trace holds a kernel of no known class: its time might be this
    class's."""
    unknown = unknown_kernels(red)
    if unknown:
        raise ValueError(f"kernels of no class in bench/kernels: {unknown}")
    kinds = red["ops"].get(cls, {})
    return sum(s for k, s in kinds.items() if k.startswith(kernel + ":"))
