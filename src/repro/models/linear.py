"""Projection factory: dense or SELL (the paper's technique) per config.

Every projection in the model zoo is created through :func:`linear_init` /
:func:`linear_apply` with a ``role`` tag (``attn_qkv``, ``attn_out``,
``mlp_in``, ``mlp_out``, ``expert`` ...).  When the role appears in
``cfg.sell_targets`` and ``cfg.sell_kind != 'dense'``, the projection is a
structured efficient linear layer — by default an order-K ACDC cascade with
TPU lane alignment — giving O(N) parameters instead of O(N^2).

This is the integration point that makes the paper's contribution a
first-class feature of the framework rather than a bolt-on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import sell as sell_mod
from repro.models.common import ModelConfig


def _sell_cfg(cfg: ModelConfig, n_in: int, n_out: int) -> sell_mod.SellConfig:
    return sell_mod.SellConfig(
        kind=cfg.sell_kind,
        n_in=n_in,
        n_out=n_out,
        k=cfg.sell_k,
        relu=cfg.sell_relu,
        permute=cfg.sell_permute,
        bias=False,  # LM convention: norms carry the biases
        init_std=cfg.sell_init_std,
        rank=cfg.sell_rank,
        method=cfg.sell_method,  # type: ignore[arg-type]
        transform=cfg.sell_transform,
        lane_multiple=128,
    )


def uses_sell(cfg: ModelConfig, role: str) -> bool:
    return cfg.sell_kind != "dense" and any(
        role.startswith(t) or t == role for t in cfg.sell_targets
    )


def linear_init(
    rng: jax.Array,
    n_in: int,
    n_out: int,
    cfg: ModelConfig,
    role: str,
    dtype=jnp.float32,
) -> dict:
    if uses_sell(cfg, role):
        scfg = _sell_cfg(cfg, n_in, n_out)
        return {"sell": sell_mod.init_sell_params(rng, scfg, dtype)}
    scale = 1.0 / np.sqrt(n_in)
    return {"w": scale * jax.random.normal(rng, (n_in, n_out), dtype)}


def _batch_axes(cfg: ModelConfig) -> tuple:
    """The mesh axes a SELL activation's batch dim shards over:
    ``cfg.sell_batch_axes`` when set, else the ``pod``/``data`` axes of
    the mesh in context (``jax.set_mesh``); empty with no mesh."""
    if cfg.sell_batch_axes:
        return tuple(cfg.sell_batch_axes)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_spec(x: jax.Array, batch_axes: tuple) -> P:
    spec = [None] * x.ndim
    spec[0] = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    return P(*spec)


def _batch_local_constraint(x: jax.Array, batch_axes: tuple) -> jax.Array:
    """Constrain a SELL input/output to batch-only sharding.

    The DCT/FFT inside a SELL mixes the ENTIRE feature axis, so if the
    activation arrives feature-sharded (tensor-parallel layout), SPMD must
    all-gather it for every transform.  Pinning SELL activations to
    (batch-sharded, feature-local) keeps the O(N log N) transform
    collective-free; the O(N) diagonals are replicated anyway.
    """
    if not batch_axes:
        return x
    return jax.lax.with_sharding_constraint(x, _batch_spec(x, batch_axes))


def _structured_linear(p: dict, x: jax.Array, scfg, batch_axes: tuple):
    """``sell.structured_linear``; on a mesh of several devices the Pallas
    kernels run under ``shard_map``, one batch shard per device, since
    XLA cannot partition a Mosaic kernel."""
    mesh = jax.sharding.get_abstract_mesh()
    if scfg.method != "pallas" or mesh.empty or mesh.size == 1:
        return sell_mod.structured_linear(p, x, scfg)
    spec = _batch_spec(x, batch_axes) if batch_axes else P()
    return jax.shard_map(
        functools.partial(sell_mod.structured_linear, cfg=scfg),
        in_specs=(P(), spec), out_specs=spec, check_vma=False)(p, x)


def linear_apply(
    params: dict,
    x: jax.Array,
    n_in: int,
    n_out: int,
    cfg: ModelConfig,
    role: str,
) -> jax.Array:
    if "sell" in params:
        scfg = _sell_cfg(cfg, n_in, n_out)
        batch_axes = _batch_axes(cfg)
        if cfg.sell_local_features:
            x = _batch_local_constraint(x, batch_axes)
        y = _structured_linear(params["sell"], x, scfg, batch_axes)
        if cfg.sell_local_features:
            y = _batch_local_constraint(y, batch_axes)
        return y
    return jnp.matmul(x, params["w"].astype(x.dtype))


def linear_param_count(cfg: ModelConfig, role: str, n_in: int, n_out: int) -> int:
    if uses_sell(cfg, role):
        return _sell_cfg(cfg, n_in, n_out).param_count()
    return n_in * n_out
