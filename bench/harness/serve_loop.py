"""Open-loop serving: the paged engine under a seeded arrival schedule.

Set-up: weights from the seed (one jitted call), the engine, one warm
request (a prefill at ``max_prompt_len`` and the decode at the pool's
shape), then the mix's own arrivals for ``warmup_s`` seconds so that the
batch is as full as the traffic keeps it.  The window follows without a
break in the arrivals.

In the loop each request is submitted when it is due, the engine ticks
while it has work, and every token a tick emits is stamped at the end of
that tick (the engine blocks on its tokens, so the stamp is when the
token exists on the host).  TTFT runs from the due time.  A request due
in the window with no first token by the window's end ranks at +inf in
the TTFT tail.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Dict, List

import jax
import numpy as np

from harness import check, program, stats, traffic, weights
from harness.trace import Tracer


def _engine_shape(config: dict, rehearse: bool) -> dict:
    s = dict(config["serve"])
    if rehearse:
        s.update(config["rehearsal"]["serve"])
    return s


def _clip_mix(mix: dict, shape: dict) -> dict:
    """The mix with its lengths held to the engine's limits and its warm
    traffic as long as the shape says (a rehearsal's smoke engine is
    smaller than the mix)."""
    mix = dict(mix, warmup_s=shape.get("warmup_s", mix["warmup_s"]))
    for key, cap in (("prompt", shape["max_prompt_len"]),
                     ("output", shape["max_new_tokens"])):
        spec = dict(mix[key])
        spec["max"] = min(spec["max"], cap)
        spec["min"] = min(spec["min"], spec["max"])
        spec["median"] = min(spec["median"], spec["max"])
        mix[key] = spec
    return mix


class Compiles:
    """Backend compilations, with the time each ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.ends: List[float] = []
        self.seconds = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        for k in self.cache:
            if event.endswith("cache_" + k):
                self.cache[k] += 1

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.ends.append(time.perf_counter())
            self.seconds += duration

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.ends)


def build(cell, seed: int, rehearse: bool, prof: bool):
    """Weights from the seed, the engine, and one warm request (a prefill
    at ``max_prompt_len`` and a decode at the pool's shape):
    ``(engine, params, cfg, shape)``."""
    from repro.obs import Observability, Prof
    from repro.serving import Engine
    from repro.serving.request import Request

    clock = Compiles()
    t0 = time.perf_counter()
    config = cell.config
    shape = _engine_shape(config, rehearse)
    cfg, model, abstract = program.serve_model(config, rehearse)
    init = weights.make_init(
        abstract, program.sizes(config, rehearse)["sell_init_std"],
        config.get("weights"))
    params = jax.block_until_ready(init(weights.seed_words(seed)))
    t1 = time.perf_counter()
    max_prompt, max_new = shape["max_prompt_len"], shape["max_new_tokens"]
    eng = Engine(model, cfg, params, n_slots=shape["slots"],
                 max_len=max_prompt + max_new + 1, max_prompt_len=max_prompt,
                 sample="greedy", paged=True, block_size=shape["block_size"],
                 n_blocks=shape["n_blocks"],
                 obs=Observability(prof=Prof(enabled=prof)),
                 clock=time.perf_counter)
    eng.submit(Request(rid=1 << 30, prompt=[1] * max_prompt,
                       max_new_tokens=2))
    while eng.has_work:
        eng.tick()
    t2 = time.perf_counter()
    print(f"[setup] weights {t1 - t0:.3f} s; engine and warm shapes "
          f"{t2 - t1:.3f} s; {len(clock.ends)} compilations "
          f"({clock.seconds:.3f} s compiling); persistent cache "
          f"{clock.cache}", file=sys.stderr, flush=True)
    return eng, params, cfg, shape
class Loop:
    """One open-loop run of a schedule through an engine: every request
    submitted when due, ticks while there is work, each emitted token
    stamped at the end of its tick."""

    def __init__(self, eng, sched, tracer):
        from repro.serving.request import Request

        self.eng, self.sched, self.tracer = eng, sched, tracer
        self.reqs = {a.rid: Request(rid=a.rid, prompt=a.prompt,
                                    max_new_tokens=a.max_new_tokens)
                     for a in sched}
        self.stamps: Dict[int, List[float]] = {a.rid: [] for a in sched}
        self.submitted: Dict[int, float] = {}
        self.live: List[int] = []
        self.i = 0

    def run(self, origin: float, until: float, trace_at: float = 0.0,
            trace_to: float = 0.0, on_time=None) -> None:
        """Drive the engine until ``until`` (perf_counter seconds); the
        schedule's due times count from ``origin``; ``on_time(now)`` is
        called once per loop turn."""
        eng, sched, tracer = self.eng, self.sched, self.tracer
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            if on_time is not None:
                on_time(now)
            tracer.window(now, trace_at, trace_to)
            with tracer.span("submit"):
                while (self.i < len(sched)
                       and origin + sched[self.i].due <= now):
                    rid = sched[self.i].rid
                    eng.submit(self.reqs[rid])
                    self.submitted[rid] = now
                    self.live.append(rid)
                    self.i += 1
            if eng.has_work:
                with tracer.span("tick"):
                    eng.tick()
                end = time.perf_counter()
                with tracer.span("bookkeeping"):
                    self._stamp(end)
            elif self.i < len(sched):
                time.sleep(max(0.0, min(origin + sched[self.i].due, until)
                               - time.perf_counter()))

    def _stamp(self, end: float) -> None:
        keep, tick = [], {"decode_contexts": [], "prefills": 0}
        for rid in self.live:
            r = self.reqs[rid]
            old = len(self.stamps[rid])
            self.stamps[rid].extend([end] * (len(r.generated) - old))
            # token j >= 1 came from a decode step over the prompt and j
            # earlier tokens; token 0 from the prefill
            for j in range(old, len(r.generated)):
                if j == 0:
                    tick["prefills"] += 1
                else:
                    tick["decode_contexts"].append(r.prompt_len + j)
            if not r.done:
                keep.append(rid)
        self.live = keep
        if self.tracer.active:
            self.tracer.ticks.append(tick)


def window_metrics(loop, origin: float, w0: float, w1: float,
                   in_window) -> dict:
    """The end-to-end numbers of the window ``[w0, w1]`` and the samples
    they come from."""
    due = {a.rid: origin + a.due for a in loop.sched}
    rids = [a.rid for a in loop.sched if in_window(a) and due[a.rid] < w1]
    out_tokens = sum(w0 <= t <= w1 for s in loop.stamps.values() for t in s)
    ttft = []
    for rid in rids:
        s = loop.stamps[rid]
        ttft.append(s[0] - due[rid] if s and s[0] <= w1 else float("inf"))
    gaps = [b - a for s in loop.stamps.values() for a, b in zip(s, s[1:])
            if w0 <= b <= w1]
    late = [loop.submitted[r] - due[r] for r in loop.submitted]
    return {"rids": rids, "out_tokens": out_tokens, "ttft": ttft,
            "gaps": gaps, "late": late}


def run(cell, args, t_start: float, rehearse: bool) -> dict:
    compiles = Compiles()
    config = cell.config
    eng, params, cfg, shape = build(cell, args.seed, rehearse,
                                    prof=bool(args.trace))
    mix = _clip_mix(cell.mix, shape) if rehearse else cell.mix
    sched = traffic.serve_schedule(mix, args.seed, args.seconds,
                                   cfg.vocab_size)
    tracer = Tracer(enabled=bool(args.trace))
    loop = Loop(eng, sched, tracer)
    origin = time.perf_counter()
    w0 = origin + float(mix["warmup_s"])
    w1 = w0 + args.seconds
    trace_at = w0 + (args.seconds - float(mix["trace_s"])) / 2
    seen = {}

    def mark(now):
        if "active_at_w0" not in seen and now >= w0:
            seen["active_at_w0"] = len(eng.scheduler.active())

    loop.run(origin, w1, trace_at, trace_at + float(mix["trace_s"]), mark)
    print(f"[setup] warm traffic {w0 - origin:.3f} s; process start to "
          f"traffic {origin - t_start:.3f} s", file=sys.stderr, flush=True)
    active_at_w0 = seen.get("active_at_w0")
    reqs, stamps = loop.reqs, loop.stamps
    trace = tracer.finish(reduce=not rehearse)
    device_peak = None

    # --- end-to-end metrics -------------------------------------------
    wm = window_metrics(loop, origin, w0, w1, lambda a: a.in_window)
    window_rids, out_tokens = wm["rids"], wm["out_tokens"]
    ttft, gaps, late = wm["ttft"], wm["gaps"], wm["late"]
    shed = [r for r in reqs.values() if r.finish_reason == "rejected"]
    broken = [r for r in reqs.values()
              if r.finish_reason in ("cache_full", "preempted_limit",
                                     "timeout")]
    print(f"[serve] window {args.seconds}s: {len(window_rids)} requests "
          f"due, {out_tokens} tokens out, {len(gaps)} token gaps; active "
          f"at window start {active_at_w0}, at end "
          f"{len(eng.scheduler.active())} of {shape['slots']} slots, queue "
          f"{len(eng.scheduler.queue)}", file=sys.stderr)
    print(f"[serve] generator lateness p50 "
          f"{stats.percentile(late, 50) * 1e3:.3f} ms, max "
          f"{max(late) * 1e3:.3f} ms; compilations inside the window "
          f"{compiles.between(w0, w1)}; engine prefills "
          f"{eng.stats['prefill_dispatches']}, decode ticks "
          f"{eng.stats['decode_ticks']}, preempted {eng.stats['preempted']}"
          f", shed {len(shed)}", file=sys.stderr)
    finite = [t for t in ttft if np.isfinite(t)]
    print(f"[serve] ttft p50 {stats.percentile(ttft, 50):.4f} s, p90 "
          f"{stats.percentile(ttft, 90):.4f} s ({len(ttft) - len(finite)} "
          f"without a first token); itl p50 "
          f"{stats.percentile(gaps, 50) * 1e3:.3f} ms, p95 "
          f"{stats.percentile(gaps, 95) * 1e3:.3f} ms, p98 "
          f"{stats.percentile(gaps, 98) * 1e3:.3f} ms ("
          f"{stats.beyond(gaps, 98)} gaps beyond), p99 "
          f"{stats.percentile(gaps, 99) * 1e3:.3f} ms", file=sys.stderr)
    # every end-to-end number a serving cell may report; BENCHMARK.json
    # names the ones each cell does
    ttft_p90 = stats.percentile(ttft, 90)
    e2e = {"ttft_p90_s": ttft_p90 if np.isfinite(ttft_p90) else 1e9,
           "itl_p50_ms": stats.percentile(gaps, 50) * 1e3,
           "itl_p98_ms": stats.percentile(gaps, 98) * 1e3,
           "out_tok_s": out_tokens / args.seconds}

    # --- free the engine, then the reference --------------------------
    if not rehearse:
        device_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in jax.devices()[:cell.chips])
    finished = [r for r in reqs.values() if r.finish_reason == "length"
                and r.t_finish is not None and w0 <= r.t_finish <= w1]
    del eng, loop
    gc.collect()
    compared = check.serve(cell, args.seed, params, finished, shape,
                           program.sizes(config, rehearse))
    attempted = len(window_rids)
    failed = sum(1 for r in shed + broken if r.rid in set(window_rids))
    return {"e2e": e2e, "records": {}, "trace": trace,
            "compared": compared, "attempted": attempted, "failed": failed,
            "memory_peak_bytes": device_peak,
            "setup_s": w0 - t_start}
