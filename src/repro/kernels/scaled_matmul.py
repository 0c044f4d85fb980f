"""Blocked scaled-matmul Pallas kernel — the "multiple call" building block.

Computes ``y = ((x * pre) @ w) * post + bias`` with a standard (m, n, k)
grid, fp32 VMEM accumulator scratch, and the diagonal scalings fused into
the k-loop so they cost no extra HBM traffic.

Two chained calls (w = C then w = C^T) implement ACDC for sizes where the
fully-fused kernel's VMEM budget is exceeded — the TPU analogue of the
paper's cuFFT-based multiple-call implementation (section 5.2), but with
the diagonal scalings folded in, so the intermediate ``h2`` round-trips HBM
exactly once instead of three extra round trips for A, D and the bias.
The DCT family's forward at N a multiple of 128 takes the factored kernel
(``acdc_factored.py``) instead; these calls remain the forward of the
other families and sizes above ``MAX_FUSED_N``, and the backward of all.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BN = 512
DEFAULT_BK = 512


def _smm_kernel(sig, nk, x_ref, w_ref, *rest):
    """Grid (m, n, k): accumulate (x*pre)[m,k] @ w[k,n] into VMEM scratch,
    finalize with post-scale and bias on the last k step."""
    refs = dict(zip(sig, rest))
    o_ref = rest[len(sig)]
    acc_ref = rest[len(sig) + 1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    if "pre" in refs:
        x = x * refs["pre"][...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finalize():
        acc = acc_ref[...]
        if "post" in refs:
            acc = acc * refs["post"][...].astype(jnp.float32)
        if "bias" in refs:
            acc = acc + refs["bias"][...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def scaled_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    pre: Optional[jax.Array] = None,
    post: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """``((x * pre) @ w) * post + bias`` for 2-D x (M, K), w (K, N)."""
    m, kdim = x.shape
    k2, n = w.shape
    assert kdim == k2, (x.shape, w.shape)
    bm = min(bm, max(8, m))
    bn = min(bn, n)
    bk = min(bk, kdim)
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-kdim) % bk
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    mm, kk = x.shape
    nn = w.shape[1]
    nk = kk // bk
    grid = (mm // bm, nn // bn, nk)

    operands = [x, w]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    sig = []
    if pre is not None:
        if pad_k:
            pre = jnp.pad(pre, ((0, pad_k),))
        operands.append(pre.reshape(1, kk))
        in_specs.append(pl.BlockSpec((1, bk), lambda i, j, k: (0, k)))
        sig.append("pre")
    if post is not None:
        if pad_n:
            post = jnp.pad(post, ((0, pad_n),))
        operands.append(post.reshape(1, nn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        sig.append("post")
    if bias is not None:
        if pad_n:
            bias = jnp.pad(bias, ((0, pad_n),))
        operands.append(bias.reshape(1, nn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        sig.append("bias")

    kernel = functools.partial(_smm_kernel, tuple(sig), nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)
    if pad_m or pad_n:
        out = out[:m, :n]
    return out
