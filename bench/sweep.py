"""Find a chat cell's knee: the highest offered rate with no growing
backlog, in one sweep over a few rates in one process.

    python bench/sweep.py --workload <cell> --seed <n> --rates 0.4,0.8,1.2 \\
        --warmup 40 --measure 40

The engine is built once (weights from the seed, the cell's shapes
warmed).  For each rate, from an empty engine: the cell's mix at that
rate for ``warmup`` seconds, then ``measure`` seconds in which the queue
of waiting requests is sampled, then the arrivals stop and the engine
drains.  One JSON line per rate: arrivals and completions in the measured
part, the queue at its start, middle and end, TTFT and token-gap
percentiles, tokens out per second.  The benchmark's own runs do not run
this; its result sets ``rate_rps`` in the traffic files, by hand.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))  # noqa: E402

from bench.run import configure_jax  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--warmup", type=float, required=True)
    ap.add_argument("--measure", type=float, required=True)
    args = ap.parse_args()
    configure_jax(rehearse=False)
    from harness import device, serve_loop, spec, stats, traffic
    from harness.trace import Tracer

    cell = spec.load_cell(args.workload)
    devs = device.require_chips(cell.chips)
    device.pin_autotune(devs[0].device_kind)
    eng, _params, cfg, shape = serve_loop.build(cell, args.seed, False,
                                                  prof=False)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_rps=rate, warmup_s=args.warmup)
        sched = traffic.serve_schedule(mix, args.seed + k, args.measure,
                                       cfg.vocab_size)
        for a in sched:
            a.rid += 1_000_000 * (k + 1)
        loop = serve_loop.Loop(eng, sched, Tracer(enabled=False))
        origin = time.perf_counter()
        w0, w1 = origin + args.warmup, origin + args.warmup + args.measure
        queue = {}

        def sample(now):
            for name, at in (("start", w0), ("mid", (w0 + w1) / 2)):
                if name not in queue and now >= at:
                    queue[name] = len(eng.scheduler.queue)

        loop.run(origin, w1, on_time=sample)
        queue["end"] = len(eng.scheduler.queue)
        active = len(eng.scheduler.active())
        wm = serve_loop.window_metrics(loop, origin, w0, w1,
                                         lambda a: a.in_window)
        done = sum(1 for r in loop.reqs.values()
                   if r.t_finish is not None and w0 <= r.t_finish <= w1)
        t_drain = time.perf_counter()
        while eng.has_work:
            eng.tick()
        print(json.dumps({
            "rate_rps": rate, "arrived": len(wm["rids"]), "completed": done,
            "queue": queue, "active_at_end": active,
            "slots": shape["slots"],
            "out_tok_s": wm["out_tokens"] / args.measure,
            "ttft_p50_s": stats.percentile(wm["ttft"], 50),
            "ttft_p90_s": stats.percentile(wm["ttft"], 90),
            "itl_p50_ms": 1e3 * stats.percentile(wm["gaps"], 50),
            "itl_p95_ms": 1e3 * stats.percentile(wm["gaps"], 95),
            "late_max_ms": 1e3 * max(wm["late"]),
            "drain_s": time.perf_counter() - t_drain,
            "device": devs[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
