"""Where the engine's host time goes, from a traced run's capture.

    python3 bench/phases.py [capture.xplane.pb]

Reduces the capture (by default the newest a ``--trace 1`` run of
``run.py`` left in the checkout) with ``harness/host.py`` and prints one
JSON object: for plain decode ticks and for ticks that admit, how many
there were and each phase's mean self time per tick (ms); spans per
tick; ``tick_host_ms`` and ``admit_host_ms`` as their readers compute
them; the share of ``tick_host_ms`` that is host time in no phase; and
the device's idle time by the phase it fell in, with the ten longest
gaps.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH / "metrics")]

from harness import host  # noqa: E402
import admit_host_ms  # noqa: E402
import tick_host_ms  # noqa: E402


def _mean_self(ticks) -> dict:
    names = sorted({n for t in ticks for n in t["self"]})
    return {n: 1e3 * sum(t["self"].get(n, 0.0) for t in ticks) / len(ticks)
            for n in names} if ticks else {}


def summary(red: dict) -> dict:
    ticks = red["ticks"]
    plain = [t for t in ticks if t["decode"] and not t["prefills"]]
    admitting = [t for t in ticks if t["prefills"]]
    per_tick = tick_host_ms.value(red)
    own = (1e3 * sum(t["self"]["engine.tick"] for t in plain) / len(plain)
           if plain else None)
    return {
        "ticks": len(ticks), "plain_ticks": len(plain),
        "admitting_ticks": len(admitting),
        "admissions": sum(t["prefills"] for t in admitting),
        "spans_per_tick": (sum(t["spans"] for t in ticks) / len(ticks)
                           if ticks else None),
        "plain_self_ms": _mean_self(plain),
        "admitting_self_ms": _mean_self(admitting),
        "tick_host_ms": per_tick,
        "admit_host_ms": admit_host_ms.value(red),
        "tick_self_share_of_tick_host": (own / per_tick if per_tick
                                         else None),
        "idle_s": red["idle"], "idle_longest": red["idle_longest"],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv:
        red = host.reduce_file(argv[0])
    else:
        red = host.load()
        if red is None:
            print("phases.py: no capture to reduce", file=sys.stderr)
            return 1
    print(json.dumps(summary(red)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
