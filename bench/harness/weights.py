"""Weights drawn from the seed, on the device, in one jitted call.

The benchmark, not the program, makes the weights: the program's
``model.init`` gives only the shapes (``jax.eval_shape``), and every leaf
is drawn here by the init kind its path names, so the reference can be
handed the same arrays without taking anything the program made.  The
kinds follow the published conventions the program documents:

* ``embedding``: N(0, 1/D), D the last axis;
* ``fan_in_normal``: a projection ``w`` (..., in, out), N(0, 1/in);
* ``acdc_identity_noise``: an ACDC diagonal, N(1, sigma^2), the paper's
  identity-plus-noise init (section 6.2), sigma the block's
  ``sell_init_std``;
* ``zero``: an RMSNorm ``scale`` (stored as scale - 1), a bias.

A leaf takes the kind of the first rule whose path suffix (whole path
segments) ends its path: first the configuration file's ``weights``
block, ``{suffix: kind}`` in its order, then ``BUILT_IN``.  A leaf that
no rule covers is an error.

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


#: init kind -> ``(key, shape, sell_std) -> leaf``
KINDS = {
    "embedding": lambda key, shape, _: (
        jax.random.normal(key, shape, jnp.float32) * shape[-1] ** -0.5),
    "fan_in_normal": lambda key, shape, _: (
        jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5),
    "acdc_identity_noise": lambda key, shape, std: (
        1.0 + std * jax.random.normal(key, shape, jnp.float32)),
    "zero": lambda key, shape, _: jnp.zeros(shape, jnp.float32),
}

#: the rules every configuration has: path suffix -> init kind
BUILT_IN = (
    ("embed/table", "embedding"),
    ("sell/a", "acdc_identity_noise"),
    ("sell/d", "acdc_identity_noise"),
    ("scale", "zero"),
    ("bias", "zero"),
    ("w", "fan_in_normal"),
)


def kind_of(path: str, rules) -> str:
    """The init kind of the leaf at ``path``: the first of ``rules``
    (``(suffix, kind)`` pairs) whose suffix ends it."""
    for suffix, kind in rules:
        if path == suffix or path.endswith("/" + suffix):
            return kind
    raise ValueError(f"no weight rule for leaf {path!r}")


def make_init(abstract_params, sell_std: float, extra: dict | None = None):
    """The jitted ``words -> params`` for the tree ``abstract_params``;
    ``extra`` is the configuration file's ``weights`` block."""
    rules = tuple((extra or {}).items()) + BUILT_IN
    for suffix, kind in rules:
        if kind not in KINDS:
            raise ValueError(f"weight rule {suffix!r}: unknown init kind "
                             f"{kind!r}; known: {sorted(KINDS)}")
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract_params)
    kinds = [KINDS[kind_of(_path(p), rules)] for p, _ in flat]
    shapes = [s.shape for _, s in flat]

    def init(words):
        key = seed_key(words)
        leaves = [draw(jax.random.fold_in(key, i), s, sell_std)
                  for i, (draw, s) in enumerate(zip(kinds, shapes))]
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(init)
