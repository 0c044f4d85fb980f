"""The configuration file's ``model`` block says what the program must be.

A configuration of another architecture loads, and draws every weight,
from files alone; ``program.check`` refuses a program that differs from
the block in any one field; and the two Qwen3 configurations give the
reference and the work counts the same numbers, bit for bit, that they
gave before their files held a ``model`` block.
"""

import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import program, weights
from work import counts

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
PINS = json.loads((DATA / "pins_before_model_block.json").read_text())
QWEN3 = ["qwen3_1_7b-acdc", "qwen3_1_7b-dense"]


def _config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("name", ["deepseek_moe_16b", "chatglm3_6b"] + QWEN3)
def test_smoke_configuration_loads_and_every_leaf_gets_a_weight(name):
    config = _config(name)
    # the published widths as the registry serves them, abstractly
    program.serve_model(config, rehearse=False)
    cfg, _, abstract = program.serve_model(config, rehearse=True)
    sizes = program.sizes(config, rehearse=True)
    params = weights.make_init(abstract, sizes["sell_init_std"],
                               config.get("weights"))(
                                   weights.seed_words(2 ** 40 + 3))
    want = jax.tree_util.tree_leaves_with_path(abstract)
    got = jax.tree_util.tree_leaves_with_path(params)
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, leaf), (_, shape) in zip(got, want):
        assert leaf.shape == shape.shape and leaf.dtype == jnp.float32, path
        assert bool(jnp.all(jnp.isfinite(leaf))), path
    assert cfg.n_layers == sizes["n_layers"]


def test_the_moe_configuration_is_one_of_routed_and_shared_experts():
    cfg, _, abstract = program.serve_model(_config("deepseek_moe_16b"),
                                           rehearse=True)
    assert (cfg.n_experts, cfg.n_shared_experts, cfg.top_k) == (8, 1, 2)
    assert not cfg.qk_norm
    paths = {weights._path(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(abstract)[0]}
    assert {"layers/moe/experts/wg/w", "layers/moe/router/w",
            "layers/moe/shared/wd/w"} <= paths


#: (field, a value other than the MoE smoke program's): fields the block
#: holds and fields it leaves at their default, of every kind
ALTERED = [
    ("n_layers", 3),                     # int, in the block
    ("n_experts", 16),                   # int, in the block
    ("top_k", 1),                        # int, in the block
    ("head_dim", None),                  # optional int, in the block
    ("norm_eps", 1e-5),                  # float, in the block
    ("dtype", "bfloat16"),               # str, in the block
    ("qk_norm", True),                   # bool, in the block
    ("qk_norm", 0),                      # a number is no bool
    ("rope_fraction", 0.5),              # float, at its default
    ("sliding_window", 64),              # int, at its default
    ("mlp_act", "gelu"),                 # str, at its default
    ("capacity_factor", 2.0),            # float, at its default
    ("sell_targets", ["attn_out"]),      # tuple, at its default
    ("sell_kind", "acdc"),               # str, in the block
    ("scan_unroll", True),               # bool, at its default
]


@pytest.fixture(scope="module")
def moe():
    config = _config("deepseek_moe_16b")
    cfg, _, _ = program.serve_model(config, rehearse=True)
    return cfg, program.sizes(config, rehearse=True)


@pytest.mark.parametrize("field,value", ALTERED,
                         ids=[f"{f}={v!r}" for f, v in ALTERED])
def test_any_one_field_changed_in_the_block_is_refused(moe, field, value):
    cfg, block = moe
    program.check(cfg, block)
    with pytest.raises(ValueError, match=field):
        program.check(cfg, dict(block, **{field: value}))


@pytest.mark.parametrize("field,value", ALTERED,
                         ids=[f"{f}={v!r}" for f, v in ALTERED])
def test_any_one_field_changed_in_the_program_is_refused(moe, field, value):
    cfg, block = moe
    if isinstance(value, list):
        value = tuple(value)
    with pytest.raises(ValueError, match=field):
        program.check(dataclasses.replace(cfg, **{field: value}), block)


def test_a_field_the_program_lacks_and_the_exempt_fields(moe):
    cfg, block = moe
    with pytest.raises(ValueError, match="no_such_field"):
        program.check(cfg, dict(block, no_such_field=1))
    program.check(dataclasses.replace(cfg, name="another", remat=False),
                  block)
    assert set(program.EXEMPT) == {"name", "remat"}


def test_a_weights_block_covers_leaves_no_built_in_rule_does():
    abstract = {"layers": {
        "attn": {"wkv_a": {"proj": jax.ShapeDtypeStruct((2, 256, 64),
                                                        jnp.float32)},
                 "kv_norm": {"gain": jax.ShapeDtypeStruct((2, 64),
                                                          jnp.float32)}},
        "moe": {"router": {"w": jax.ShapeDtypeStruct((2, 256, 8),
                                                     jnp.float32)}}}}
    words = weights.seed_words(5)
    with pytest.raises(ValueError, match="no weight rule"):
        weights.make_init(abstract, 0.061)
    with pytest.raises(ValueError, match="unknown init kind"):
        weights.make_init(abstract, 0.061, {"wkv_a/proj": "xavier"})
    block = {"wkv_a/proj": "fan_in_normal", "kv_norm/gain": "zero",
             "router/w": "zero"}
    p = weights.make_init(abstract, 0.061, block)(words)
    proj = np.asarray(p["layers"]["attn"]["wkv_a"]["proj"])
    assert abs(proj.std() * 256 ** 0.5 - 1.0) < 0.05
    assert not np.any(np.asarray(p["layers"]["attn"]["kv_norm"]["gain"]))
    # the file's rules come before the built-in ones
    assert not np.any(np.asarray(p["layers"]["moe"]["router"]["w"]))
    # a suffix matches whole path segments only
    assert weights.kind_of("layers/moe/router/w", weights.BUILT_IN) == \
        "fan_in_normal"
    with pytest.raises(ValueError):
        weights.kind_of("layers/attn/kv_a", (("v_a", "zero"),))


def _hex(values):
    return [v.hex() if isinstance(v, float) else v for v in values]


@pytest.mark.parametrize("name", QWEN3)
@pytest.mark.parametrize("part", ["model", "rehearsal"])
def test_work_counts_equal_the_pinned_ones(name, part):
    sizes = program.sizes(_config(name), rehearse=part == "rehearsal")
    pin = PINS[name][part]
    assert _hex(counts.token_flops(sizes, c)
                for c in PINS["contexts"]) == pin["token_flops"]
    assert _hex(counts.attn_bytes(sizes, c, 2)
                for c in PINS["contexts"]) == pin["attn_bytes"]
    assert _hex(counts.attn_flops(sizes, c)
                for c in PINS["attn_flops_contexts"]) == pin["attn_flops"]
    assert _hex(x for args in PINS["sell_work_args"]
                for x in counts.sell_work(sizes, *args)) == pin["sell_work"]


@pytest.mark.parametrize("name", QWEN3)
def test_reference_gaps_equal_the_pinned_ones_bit_for_bit(name):
    out = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "one_core_gaps.py"), name],
        capture_output=True, text=True, timeout=600, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    for key in ("gap", "rank", "gap_fp8"):
        assert got[key] == PINS[name][key], key
