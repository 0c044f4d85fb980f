"""The reference's token gaps at a configuration's rehearsal sizes, on one
CPU core, as one JSON line: ``{"gap", "rank", "gap_fp8"}``, floats in hex.

    python bench/tests/one_core_gaps.py <configuration name>

XLA's CPU backend splits a matmul's sums by the cores it sees, so its
last bits follow the core count; on one core, with Eigen's own threads
off, they repeat from run to run.  ``test_config_block`` compares them,
bit for bit, with the numbers the harness gave before the configuration
files held a ``model`` block.
"""

import os
import sys

if __name__ == "__main__":
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false")
    os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH / "metrics", BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: the seed of the weights and of the tokens
SEED, TOKEN_SEED = 4242424242, 7


def gaps(config_name: str) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from harness import program, spec, weights

    config = json.loads((BENCH / "configs" / f"{config_name}.json")
                        .read_text())
    ref = spec.load_module(BENCH / "configs" / config["reference"],
                           f"reference_{config_name}")
    sizes = program.sizes(config, rehearse=True)
    _, _, abstract = program.serve_model(config, rehearse=True)
    params = weights.make_init(abstract, sizes["sell_init_std"],
                               config.get("weights"))(
                                   weights.seed_words(SEED))
    rng = np.random.default_rng(TOKEN_SEED)
    tokens = jnp.asarray(rng.integers(0, sizes["vocab_size"], 48),
                         jnp.int32)
    rows = jnp.arange(15, 47, dtype=jnp.int32)
    targets = tokens[16:48]
    mats = ref.transform_mats(sizes)
    got = ref.token_gaps(params, mats, tokens, rows, targets, sizes)
    fp8 = ref.token_gaps(params, mats, tokens, rows, targets, sizes, "fp8")
    return {"gap": [float(x).hex() for x in np.asarray(got["gap"])],
            "rank": [int(x) for x in np.asarray(got["rank"])],
            "gap_fp8": [float(x).hex() for x in np.asarray(fp8["gap"])]}


if __name__ == "__main__":
    print(json.dumps(gaps(sys.argv[1])))
