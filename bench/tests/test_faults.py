"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run (``run.main`` with ``rehearse``: no
look for a chip, the configuration's smoke sizes on the CPU) with one
fault planted in the program, and reads ``correct`` from the result
line.  A clean rehearsal of the same cell is correct.  The chat cells
can have one of the faults a run must catch, a token altered where it
is produced; one chip has no exchange between chips, and a serving cell
no training state or batch mean.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from repro.dist import steps as steps_mod


def _result(capsys, argv):
    assert bench_run.main(argv, rehearse=True) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


CELLS = ["qwen3_1_7b-acdc.chat", "qwen3_1_7b-dense.chat"]


def _chat(cell):
    return ["--workload", cell, "--seed", "4242424242", "--seconds", "4",
            "--trace", "0"]


@pytest.mark.parametrize("cell", CELLS)
def test_clean_chat_is_correct(capsys, cell):
    res = _result(capsys, _chat(cell))
    assert res["correct"] is True
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_not_correct(capsys, monkeypatch, cell):
    make = steps_mod.make_serve_step

    def altered(*a, **k):
        step = make(*a, **k)

        def wrong(*args):
            tok, cache = step(*args)
            # every slot's token replaced by its neighbour id
            return (tok + 1) % 512, cache

        return wrong

    monkeypatch.setattr(steps_mod, "make_serve_step", altered)
    res = _result(capsys, _chat(cell))
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["compared"].values())


@pytest.mark.parametrize("cell_name", ["qwen3_1_7b-acdc.chat",
                                       "qwen3_1_7b-dense.chat"])
def test_fp8_control_is_not_correct(cell_name):
    """The control, the reference in float8 put in the program's place,
    fails the served-token comparison on each of three seeds: at the smoke
    widths with the published depth of 28 layers, on 256 tokens that the
    float32 reference scores (the program at these sizes serves in
    float32 and reads 0)."""
    import dataclasses

    import jax

    from harness import check, program, spec, weights

    cell = spec.load_cell(cell_name)
    ref = cell.reference()
    sizes = dict(program.sizes(cell.config, rehearse=True), n_layers=28)
    cfg, model, _ = program.serve_model(cell.config, rehearse=True)
    cfg = dataclasses.replace(cfg, n_layers=28)
    abstract = jax.eval_shape(lambda k: model.init(k, cfg),
                              jax.random.PRNGKey(0))
    mats = ref.transform_mats(sizes)
    for seed in (11, 12, 13):
        params = weights.make_init(abstract, 0.061)(weights.seed_words(seed))
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.integers(0, sizes["vocab"], 320), jnp.int32)
        rows = jnp.arange(63, 319, dtype=jnp.int32)
        got = check.gap_stats({k: np.asarray(v) for k, v in ref.token_gaps(
            params, mats, tokens, rows, tokens[64:320], sizes,
            quant="fp8").items()})
        items = check.items(cell, got)
        assert items and not check.is_correct(items), (seed, got)
