"""Everything a run needs, found by name from ``BENCHMARK.json``.

A cell names its configuration and its traffic mix; each lives in a file
of its own under ``bench/``, and a later cell, mix, metric or kernel is
added as files and entries: no file already under ``bench/`` changes.

``configs/<config>.json``, the configuration as it is run:

* ``program``: ``arch``, the name ``launch/serve.py --arch`` takes;
* ``model``: the ``ModelConfig`` fields (``models/common.py``) the
  program must have, by their field names.  ``harness.program.check``
  compares every field of the served configuration with it; a field the
  block leaves out must hold its dataclass default, and only
  ``program.EXEMPT`` may differ.  The reference and the work counts read
  their sizes from it (``program.sizes``), and ``sell_init_std`` is the
  sigma of the ACDC diagonals' init;
* ``weights`` (optional): ``{path suffix: init kind}`` for leaves that
  ``harness.weights.BUILT_IN`` does not cover, the kinds those of
  ``weights.KINDS``;
* ``serve``: the engine's shape (slots, pages, prompt and output limits,
  the number of finished requests the check samples);
* ``limits``: each number ``harness.check`` compares, with its limit;
* ``rehearsal``: a ``model`` block at the registry's smoke sizes and the
  ``serve`` keys that change, for ``--rehearse`` on the CPU;
* ``reference``: the file name, beside the configuration, of its plain
  reference, a module that imports nothing of the program and gives
  ``transform_mats(cfg) -> mats`` (any constants it needs, made once a
  run) and ``token_gaps(params, mats, tokens, rows, targets, cfg,
  quant=None) -> {"gap", "rank"}`` (``harness.check.served_gaps``), where
  ``cfg`` is the ``model`` block;
* ``published``: the source's own ``config.json`` keys, for the record
  (nothing checks against it), with ``reduced`` and ``assumed`` beside it.

The rest, each found by its name:

* ``traffic/<mix>.json``: the parameters the one generator reads;
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``
  for each per-layer metric;
* ``kernels/<class>.json``: a kernel class, the regular expressions of
  the custom-call names that belong to it (``harness.trace``);
* ``work/<module>.py``: the operations and bytes a configuration's
  layers require.  ``work/counts.py`` counts the layers of the Qwen3
  configurations; a configuration with other layers brings a module of
  its own beside it, read by metric files of its own.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List

#: the benchmark's own directory; ``BENCHMARK.json`` sits beside it
BENCH = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]       # this cell's end-to-end metric entries
    per_layer: List[dict]        # this cell's per-layer metric entries
    run_seconds: int
    root: Path                   # the bench directory the files came from

    def reference(self):
        """The configuration's plain reference module."""
        return load_module(self.root / "configs" / self.config["reference"],
                           f"reference_{self.config['name']}")

    def reader(self, metric: str) -> Callable:
        if str(self.root / "metrics") not in sys.path:
            sys.path.insert(0, str(self.root / "metrics"))
        return load_module(self.root / "metrics" / f"{metric}.py",
                           f"metric_{metric}").read


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(name: str, root: Path = BENCH) -> Cell:
    """The cell ``name`` of ``<root>/../BENCHMARK.json`` with its files."""
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    config = json.loads((root / "configs" / f"{w['config']}.json")
                        .read_text())
    mix = json.loads((root / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]), root=root)
