"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix
and per-layer metric readers are files under ``bench/`` found by name
(``harness/spec.py``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace taken
in the middle of the window.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``busy_s`` and ``window_s`` when traced), ``breakdown``
when traced, and last ``compared``: each number the correctness check
compared, beside its limit.  The same numbers end standard error.

Kernel blocks that the program would time at its first run in a checkout
are pinned from ``autotune/<device_kind>.json`` (``harness/device.py``),
so that every checkout runs the same kernels.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  ``--rehearse`` runs the cell's loops and checks
on whatever backend JAX has (the CPU here) at the configuration's smoke
sizes, skips the look for a chip, and reports no device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed directory of the checkout
COMPILE_CACHE = ROOT / ".cache" / "jax"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at smoke sizes: no chip needed, no "
                         "device metric reported")
    return ap.parse_args(argv)


def configure_jax(rehearse: bool) -> None:
    """Every compiled program of the run goes to, and comes from, the
    checkout's cache, whatever its size or compile time (a rehearsal
    keeps no cache)."""
    if rehearse:
        return
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def per_layer(cell, out: dict, sizes: dict, kind: str) -> dict:
    from harness import device

    run = {"trace": out["trace"], "records": out["records"], "sizes": sizes,
           "elem": 2 if cell.config["program"]["dtype"] == "bfloat16" else 4,
           "peak": device.peaks(kind), "e2e": out["e2e"]}
    metrics = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None, rehearse: bool = False) -> int:
    args = parse_args(argv)
    rehearse = rehearse or args.rehearse
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH / "metrics"))
    configure_jax(rehearse)
    from harness import check, device, program, serve_loop, spec, trace

    cell = spec.load_cell(args.workload, BENCH)
    if not rehearse:
        try:
            devs = device.require_chips(cell.chips)
            device.peaks(devs[0].device_kind)
        except (device.NoChip, KeyError) as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 3
        print(f"[run] autotune pinned: "
              f"{device.pin_autotune(devs[0].device_kind)}", file=sys.stderr)
    loop = {"serve": serve_loop}[cell.mix["kind"]]
    out = loop.run(cell, args, T_START, rehearse)
    correct = check.is_correct(out["compared"])
    sizes = program.sizes(cell.config, rehearse)

    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}}
    if rehearse:
        import jax
        result["device"] = {"platform": jax.devices()[0].platform,
                            "rehearsal": True}
    else:
        result["device"] = device.describe(devs)
        result["device"]["memory_peak_bytes"] = out["memory_peak_bytes"]
        if args.trace:
            red = out["trace"]
            result["metrics"] = per_layer(cell, out, sizes,
                                          devs[0].device_kind)
            result["device"].update(busy_s=red["busy_s"],
                                    window_s=red["window_s"])
            result["breakdown"] = trace.breakdown(red)
        else:
            values = dict(out["e2e"], setup_s=out["setup_s"])
            result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                             "unit": m["unit"]}
                                 for m in cell.end_to_end}
    if out["trace"] is not None and "idle" in out["trace"]:
        print(f"[trace] idle by host activity (s): {out['trace']['idle']}; "
              f"device calls per class: "
              f"{ {k: len(v) for k, v in out['trace']['programs'].items()} }"
              f"; kernels of no known class: "
              f"{trace.unknown_kernels(out['trace'])}", file=sys.stderr)
    print(f"[run] setup_s {out['setup_s']!r}", file=sys.stderr)
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]}
                          for c in out["compared"]}
    check.report(out["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
