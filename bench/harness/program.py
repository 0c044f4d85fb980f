"""The system under test, reached only through its entry points.

``launch/serve.build_model`` gives the served configuration and model (its
weights are traced abstractly for their shapes and never made).  It is
held to the configuration file's ``model`` block (``rehearsal.model`` in a
rehearsal): every field of the program's ``ModelConfig`` equals the
block's value, or its dataclass default where the block leaves it out, so
the file is the configuration as it is run.
"""

from __future__ import annotations

import dataclasses
import sys

import jax

from harness.spec import BENCH

SRC = BENCH.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

#: ``ModelConfig`` fields that cannot change a served number, so the block
#: need not hold them
EXEMPT = (
    "name",    # a label
    "remat",   # rematerialises activations for a backward pass; serving
               # runs none
)


def sizes(config: dict, rehearse: bool) -> dict:
    """The configuration file's ``model`` block, or its rehearsal's: the
    ``ModelConfig`` fields the program must have, by their names in
    ``models/common.py``, and what the reference and the counts read.
    Lists come back as tuples, as the dataclass holds them."""
    block = config["rehearsal"]["model"] if rehearse else config["model"]
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in block.items()}


def _same(have, want) -> bool:
    """Equal in value and kind: a bool is no number, a tuple no string."""
    if isinstance(have, bool) or isinstance(want, bool):
        return type(have) is type(want) and have == want
    if isinstance(have, (tuple, list)) or isinstance(want, (tuple, list)):
        return (isinstance(have, (tuple, list))
                and isinstance(want, (tuple, list))
                and len(have) == len(want)
                and all(_same(h, w) for h, w in zip(have, want)))
    if isinstance(have, (int, float)) and isinstance(want, (int, float)):
        return float(have) == float(want)
    return type(have) is type(want) and have == want


def check(cfg, want: dict) -> None:
    """Raise unless every field of the program's ``cfg`` outside
    ``EXEMPT`` is the block ``want``'s, or its default where ``want``
    leaves it out, and ``want`` names no field ``cfg`` lacks."""
    fields = {f.name: f for f in dataclasses.fields(cfg)}
    have = dataclasses.asdict(cfg)
    diff = {k: (None, v) for k, v in want.items() if k not in fields}
    for name, f in fields.items():
        if name in EXEMPT:
            continue
        default = (f.default_factory()
                   if f.default is dataclasses.MISSING else f.default)
        w = want.get(name, default)
        if not _same(have[name], w):
            diff[name] = (have[name], w)
    if diff:
        raise ValueError(f"the program's configuration differs from the "
                         f"file (have, want): {diff}")


def _argv(config: dict, rehearse: bool) -> list:
    model = sizes(config, rehearse)
    argv = ["--arch", config["program"]["arch"],
            "--sell", model.get("sell_kind", "dense")]
    if "sell_method" in model:
        argv += ["--sell-method", model["sell_method"]]
    return argv + (["--smoke"] if rehearse else [])


def serve_model(config: dict, rehearse: bool):
    """``(cfg, model, abstract_params)`` from ``launch/serve.build_model``."""
    from repro.launch import serve

    args = serve.parse_args(_argv(config, rehearse))
    box = {}

    def build(key):
        cfg, model, params = serve.build_model(args, key)
        box["cfg"], box["model"] = cfg, model
        return params

    abstract = jax.eval_shape(build, jax.random.PRNGKey(0))
    check(box["cfg"], sizes(config, rehearse))
    return box["cfg"], box["model"], abstract
