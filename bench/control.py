"""Readings that set the correctness limits: the program's and the
control's, over many seeds in one process.

    python bench/control.py --workload <cell> --seeds 101,102,... \\
        [--seconds S] [--rehearse]

The benchmark's own runs do not run this.  For each seed, an engine with
weights from that seed serves the cell's mix at its rate for
``warmup_s`` plus ``--seconds`` seconds, and its finished requests are
sampled as a run samples them.  On that sample it prints one JSON line:
the statistics (``check.gap_stats``) of the served tokens under the
float32 reference (the program's readings) and of the tokens that the
float8 reference (the control: every matmul operand rounded to e4m3)
puts first, each with ``correct`` as a run would decide it
(``check.is_correct`` against the configuration's limits).  It exits 1
unless the program is correct and the control is not on every seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from bench.run import configure_jax  # noqa: E402


def serve_readings(cell, seeds, seconds: float, rehearse: bool) -> bool:
    """Print each seed's readings; true when every seed's program is
    correct and every seed's control is not."""
    from harness import check, program, serve_loop, traffic
    from harness.trace import Tracer

    ref = cell.reference()
    sizes = program.sizes(cell.config, rehearse)
    separated = True
    for seed in seeds:
        # a fresh engine for each seed: nothing of the last one stays on
        # the device beside the reference
        eng, params, cfg, shape = serve_loop.build(cell, seed, rehearse,
                                                     prof=False)
        mix = (serve_loop._clip_mix(cell.mix, shape) if rehearse
               else cell.mix)
        sched = traffic.serve_schedule(mix, seed, seconds, cfg.vocab_size)
        loop = serve_loop.Loop(eng, sched, Tracer(enabled=False))
        origin = time.perf_counter()
        w0 = origin + float(mix["warmup_s"])
        loop.run(origin, w0 + seconds)
        w1 = time.perf_counter()
        finished = [r for r in loop.reqs.values()
                    if r.finish_reason == "length" and r.t_finish is not None
                    and w0 <= r.t_finish <= w1]
        del eng, loop
        gc.collect()
        reqs = check.sample_requests(finished, seed, shape["check_sample"])
        line = {"seed": seed, "requests": len(reqs),
                "tokens": sum(len(r.generated) for r in reqs)}
        for name, quant in (("program", None), ("control_fp8", "fp8")):
            got = check.gap_stats(check.served_gaps(
                ref, params, reqs, sizes, shape["max_prompt_len"],
                shape["max_new_tokens"], quant=quant))
            line[name] = dict(got, correct=check.is_correct(
                check.items(cell, got)))
        separated &= (line["program"]["correct"]
                      and not line["control_fp8"]["correct"])
        print(json.dumps(line), flush=True)
        del params, finished, reqs
        gc.collect()
    return separated


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    configure_jax(args.rehearse)
    from harness import device, spec

    cell = spec.load_cell(args.workload)
    if not args.rehearse:
        devs = device.require_chips(cell.chips)
        device.pin_autotune(devs[0].device_kind)
    seeds = [int(s) for s in args.seeds.split(",")]
    return 0 if serve_readings(cell, seeds, args.seconds,
                               args.rehearse) else 1


if __name__ == "__main__":
    sys.exit(main())
