"""Regenerate the ``family='acdc'`` bit-identity goldens.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tests/goldens/gen_acdc_goldens.py

Captures, on CPU (the CI backend the pins run on):

* greedy continuous-batching engine token streams for the qwen3 smoke
  config with ACDC SELL projections on the fused Pallas path, and
* raw fused-cascade VJP cotangents (dx/da/dd) for a fixed operand set,

into ``acdc_goldens.json``.  ``tests/test_families.py`` asserts the live
code reproduces both EXACTLY (token equality, bitwise float equality) —
the guard that the pluggable-transform refactor left the paper's DCT
family untouched.  Only regenerate after an intentional numerics change
or a JAX upgrade (the file records the version that wrote it).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np


def engine_streams():
    import dataclasses

    from repro.configs import registry
    from repro.models import get_model
    from repro.serving import Engine, Request

    cfg = registry.get_smoke_config("qwen3_1_7b")
    cfg = dataclasses.replace(cfg, sell_kind="acdc", sell_method="pallas")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(7)
    reqs = [
        Request(rid=i,
                prompt=rs.randint(0, cfg.vocab_size,
                                  size=rs.randint(4, 12)).tolist(),
                max_new_tokens=8)
        for i in range(5)
    ]
    eng = Engine(model, cfg, params, n_slots=2, max_len=24,
                 max_prompt_len=12)
    eng.run(reqs, max_ticks=400)
    return {
        "prompts": [r.prompt for r in reqs],
        "generated": [list(map(int, r.generated)) for r in reqs],
    }


def cascade_grads():
    from repro.kernels import ops

    n, k, m = 128, 3, 8
    r = jax.random.PRNGKey(41)
    x = jax.random.normal(r, (m, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (k, n))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (k, n))
    b = 0.05 * jax.random.normal(jax.random.fold_in(r, 3), (k, n))
    g = jax.random.normal(jax.random.fold_in(r, 4), (m, n))

    y, vjp = jax.vjp(
        lambda x, a, d, b: ops.acdc_cascade_op(x, a, d, b, relu=True,
                                               permute=True), x, a, d, b)
    dx, da, dd, db = vjp(g)

    def pin(arr):
        flat = np.asarray(arr, np.float32).ravel()
        # first 8 raw IEEE words (bitwise pin) + a float64 checksum
        return {
            "head_bits": [int(w) for w in
                          flat[:8].view(np.uint32)],
            "checksum": float(np.float64(flat).sum()),
        }

    return {
        "y": pin(y), "dx": pin(dx), "da": pin(da), "dd": pin(dd),
        "db": pin(db),
    }


def main():
    out = {
        "backend": jax.default_backend(),
        # jax.random draws and CPU codegen both change between releases:
        # the pins hold for the version that wrote them
        "jax_version": jax.__version__,
        "engine": engine_streams(),
        "cascade_vjp": cascade_grads(),
    }
    path = os.path.join(os.path.dirname(__file__), "acdc_goldens.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
