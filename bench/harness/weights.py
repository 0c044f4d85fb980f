"""Weights drawn from the seed, on the device, in one jitted call.

The benchmark, not the program, makes the weights: the program's
``model.init`` gives only the shapes (``jax.eval_shape``), and every leaf
is drawn here by a rule on its path, so the reference can be handed the
same arrays without taking anything the program made.  The rules follow
the published conventions the program documents:

* ``embed/table``: N(0, 1/D);
* a dense projection ``w`` (in, out): N(0, 1/in);
* an ACDC diagonal ``sell/a``, ``sell/d``: N(1, sigma^2), the paper's
  identity-plus-noise init (section 6.2), sigma from the configuration;
* an RMSNorm ``scale`` (stored as scale - 1) and a ``sell/bias``: zero.

Leaves are fp32, the master type the program serves from.  The seed may
exceed 32 bits: its low and high words go in as data, so one compiled
program serves every seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """``seed`` as two uint32 words, low first."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def seed_key(words: jax.Array) -> jax.Array:
    key = jax.random.PRNGKey(words[0])
    return jax.random.fold_in(key, words[1])


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _leaf(path: str, shape, key, sell_std: float):
    name = path.rsplit("/", 1)[-1]
    if path.endswith("embed/table"):
        return jax.random.normal(key, shape, jnp.float32) * shape[-1] ** -0.5
    if name in ("a", "d") and "/sell/" in path:
        return 1.0 + sell_std * jax.random.normal(key, shape, jnp.float32)
    if name in ("scale", "bias"):
        return jnp.zeros(shape, jnp.float32)
    if name == "w":
        return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    raise ValueError(f"no weight rule for leaf {path!r}")


def make_init(abstract_params, sell_std: float):
    """The jitted ``words -> params`` for the tree ``abstract_params``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract_params)
    paths = [_path(p) for p, _ in flat]
    shapes = [s.shape for _, s in flat]

    def init(words):
        key = seed_key(words)
        leaves = [_leaf(p, s, jax.random.fold_in(key, i), sell_std)
                  for i, (p, s) in enumerate(zip(paths, shapes))]
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(init)
