"""Size each configuration's paged pool ahead of time, for a described v5e
chip (no chip needed).

    JAX_PLATFORMS=cpu python bench/sizing.py

For each serving configuration it compiles the engine's prefill and
decode programs at the real widths for one chip of a described
``v5e:2x2`` topology and reads ``compiled.memory_analysis()``.  The pool
takes what is left of the chip's ``bytes_limit`` after the parameters
(fp32 masters), the programs' temporaries (which grow with the pool),
both programs' code and constants and the batch-1 prefill slab, less a
margin; slots are pool tokens over the longest request, rounded down to
a multiple of 8.  The numbers go into the configuration files by hand,
with this arithmetic beside them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import program, spec  # noqa: E402

#: ``bytes_limit`` of one v5e chip as ``memory_stats()`` reports it
BYTES_LIMIT = 16_909_336_064
#: held back for the allocator's fragmentation and small buffers
MARGIN = 512 << 20


def _describe():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=chip), tree)


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes")}


def _compile_serve(config: dict, chip, blocks: int):
    """The engine's prefill and decode programs over a pool of ``blocks``
    pages: ``(memory of prefill, of decode, facts)``."""
    from repro.dist import steps as steps_mod
    from repro.kernels import ops

    ops.interpret_mode = lambda: False
    cfg, model, abstract = program.serve_model(config, rehearse=False)
    s = config["serve"]
    bs = s["block_size"]
    mb = -(-(s["max_prompt_len"] + s["max_new_tokens"] + 1) // bs)
    slots = max(8, blocks // mb // 8 * 8)
    params = _on(chip, abstract)
    cache = _on(chip, jax.eval_shape(
        lambda: model.init_cache_paged(cfg, slots, blocks, bs)))
    slab = _on(chip, jax.eval_shape(lambda: model.init_cache(cfg, 1,
                                                             mb * bs)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa
                                              sharding=chip)
    prefill = jax.jit(steps_mod.make_prefill_step(model, cfg, paged=True),
                      donate_argnums=(1,)).lower(
        params, cache, slab, i32(1, s["max_prompt_len"]), i32(1), i32(mb),
        i32()).compile()
    key = _on(chip, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    decode = jax.jit(steps_mod.make_serve_step(model, cfg, paged=True),
                     donate_argnums=(1,)).lower(
        params, cache, i32(slots), i32(slots), i32(slots, mb),
        key).compile()
    size = lambda tree: sum(x.size * x.dtype.itemsize  # noqa: E731
                            for x in jax.tree.leaves(tree))
    facts = {"param_bytes": size(abstract), "slab_bytes": size(slab),
             "pool_bytes": size(cache), "slots_compiled": slots,
             "kv_bytes_per_token": size(cache) // ((blocks + 1) * bs)}
    return _mem(prefill), _mem(decode), facts


def serve(config: dict, chip) -> dict:
    """Fit each program's temporaries as ``t0 + a * pool`` from compiles
    at two pool sizes (the decode program holds a second copy of the pool
    as temporaries), solve ``params + pool + temp(pool) + code + slab +
    margin = bytes_limit``, and compile again at the solution."""
    s = config["serve"]
    bs, longest = s["block_size"], s["max_prompt_len"] + s["max_new_tokens"]
    mb = -(-(longest + 1) // bs)
    points = [_compile_serve(config, chip, n * mb) for n in (8, 16)]
    (p1, d1, f1), (p2, d2, f2) = points
    code = p2["generated_code_size_in_bytes"] + d2[
        "generated_code_size_in_bytes"]
    fixed = f2["param_bytes"] + f2["slab_bytes"] + code + MARGIN
    best = None
    for m1, m2 in ((p1, p2), (d1, d2)):
        a = (m2["temp_size_in_bytes"] - m1["temp_size_in_bytes"]) / (
            f2["pool_bytes"] - f1["pool_bytes"])
        t0 = m1["temp_size_in_bytes"] - a * f1["pool_bytes"]
        pool = (BYTES_LIMIT - fixed - t0) / (1 + a)
        best = pool if best is None else min(best, pool)
    pool_tokens = int(best) // f2["kv_bytes_per_token"]
    slots = pool_tokens // longest // 8 * 8
    n_blocks = pool_tokens // bs
    p3, d3, f3 = _compile_serve(config, chip, n_blocks)
    used = (f3["param_bytes"] + f3["pool_bytes"] + f3["slab_bytes"] + code
            + max(p3["temp_size_in_bytes"], d3["temp_size_in_bytes"]))
    return {"bytes_limit": BYTES_LIMIT, "margin": MARGIN, "code": code,
            "points": points, "pool_tokens": pool_tokens, "slots": slots,
            "n_blocks": n_blocks, "at_solution": [p3, d3, f3],
            "bytes_at_solution": used}


def main() -> None:
    argparse.ArgumentParser(description=__doc__.split("\n")[0]).parse_args()
    chip = _describe()
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        if cell.mix["kind"] == "serve":
            print(w["config"], json.dumps(serve(cell.config, chip)),
                  flush=True)


if __name__ == "__main__":
    main()
