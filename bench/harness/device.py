"""The accelerator the run is on, and its published peaks."""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Optional

import jax

BENCH = Path(__file__).resolve().parents[1]
PEAKS = BENCH / "peaks.json"
#: autotune winners pinned per device kind (``tune.py`` makes them)
PINS = BENCH / "autotune"
#: the program's autotune cache file as the benchmark gives it: a fixed
#: path of the checkout, beside the compilation cache
AUTOTUNE_CACHE = BENCH.parent / ".cache" / "bench_autotune.json"
#: where the program reads the path of its autotune cache file
AUTOTUNE_ENV = "REPRO_AUTOTUNE_CACHE_PATH"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


def peaks(kind: str) -> dict:
    """The peak rates of ``device_kind`` ``kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[kind]


def describe(devs) -> dict:
    """The result line's ``device`` block: platform, kind, count and the
    peak bytes in use on the fullest chip."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def pin_autotune(kind: str, path: Path = AUTOTUNE_CACHE) -> Optional[dict]:
    """Give the program the autotune winners pinned for ``device_kind``
    ``kind`` and return them, or None where the kind has none.

    The program times some kernels' blocks at its first run in a checkout
    and keeps the winners in its cache file; a timing that differs between
    checkouts would make two checkouts of one tree run different kernels.
    The pinned entries are written over that file (at ``path``, named to
    the program by ``REPRO_AUTOTUNE_CACHE_PATH``) before every run, so the
    program times none of them; entries it times itself, for keys that
    are not pinned, stay in the file from run to run as the program keeps
    them.
    """
    pins = PINS / (kind.replace(" ", "_") + ".json")
    if not pins.is_file():
        return None
    pinned = json.loads(pins.read_text())
    entries = {}
    try:
        old = json.loads(path.read_text())
        if old.get("backend") == pinned["backend"]:
            entries = dict(old.get("entries", {}))
    except (OSError, ValueError):
        pass
    entries.update(pinned["entries"])
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({"backend": pinned["backend"],
                                "entries": entries}, indent=2,
                               sort_keys=True))
    os.environ[AUTOTUNE_ENV] = str(path)
    return dict(pinned["entries"])
