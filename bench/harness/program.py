"""The system under test, reached only through its entry points.

``launch/serve.build_model`` gives the served configuration and model (its
weights are traced abstractly for their shapes and never made).  It is
held to the configuration file: a size or switch that differs from the
file is an error, so the file is the configuration as it is run.
"""

from __future__ import annotations

import sys

import jax

from harness.spec import BENCH

SRC = BENCH.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


def sizes(config: dict, rehearse: bool) -> dict:
    """The model sizes the reference and the counts use: the published
    ones, or the rehearsal's smoke sizes."""
    if rehearse:
        return dict(config["rehearsal"]["sizes"])
    p = config["published"]
    return {"n_layers": p["num_hidden_layers"], "d_model": p["hidden_size"],
            "n_heads": p["num_attention_heads"],
            "n_kv_heads": p["num_key_value_heads"], "head_dim": p["head_dim"],
            "d_ff": p["intermediate_size"], "vocab": p["vocab_size"],
            "rope_theta": float(p["rope_theta"]),
            "norm_eps": float(p["rms_norm_eps"]),
            "sell": config["program"]["sell"]}


def check(cfg, want: dict, program: dict) -> None:
    """Raise unless the program's ``cfg`` is the configuration file's."""
    have = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "rope_theta": float(cfg.rope_theta),
            "norm_eps": float(cfg.norm_eps), "sell": cfg.sell_kind}
    diff = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    switches = {"qk_norm": (cfg.qk_norm, True),
                "rope_fraction": (cfg.rope_fraction, 1.0),
                "sliding_window": (cfg.sliding_window, 0),
                "n_experts": (cfg.n_experts, 0),
                "mlp_act": (cfg.mlp_act, "silu"),
                "attn_logit_softcap": (cfg.attn_logit_softcap, 0.0)}
    if cfg.sell_kind == "acdc":
        switches.update({
            "sell_k": (cfg.sell_k, program["sell_k"]),
            "sell_method": (cfg.sell_method, program["sell_method"]),
            "sell_permute": (cfg.sell_permute, True),
            "sell_relu": (cfg.sell_relu, False),
            "sell_transform": (cfg.sell_transform, "acdc"),
            "sell_targets": (tuple(cfg.sell_targets)[:2],
                             ("attn_out", "mlp"))})
    diff.update({k: v for k, v in switches.items() if v[0] != v[1]})
    if diff:
        raise ValueError(f"the program's configuration differs from the "
                         f"file (have, want): {diff}")


def _argv(config: dict, rehearse: bool) -> list:
    p = config["program"]
    argv = ["--arch", p["arch"], "--sell", p["sell"]]
    if p["sell"] != "dense":
        argv += ["--sell-method", p["sell_method"]]
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
    check(box["cfg"], sizes(config, rehearse), config["program"])
    if box["cfg"].dtype != config["program"]["dtype"] and not rehearse:
        raise ValueError(f"served dtype {box['cfg'].dtype}, the file says "
                         f"{config['program']['dtype']}")
    return box["cfg"], box["model"], abstract
