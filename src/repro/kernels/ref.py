"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package is validated (interpret mode on CPU, compiled
on TPU) against the functions here with ``assert_allclose`` over shape and
dtype sweeps — see ``tests/test_kernels.py``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import transforms


def acdc_fused_ref(
    x: jax.Array,
    a: jax.Array,
    d: jax.Array,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Oracle for the fused ACDC kernel: ``y = ((x*a) C * d + bias) C^T``.

    Computed with the explicit orthonormal DCT matrix in float32.
    """
    n = x.shape[-1]
    c = transforms.dct_matrix(n, dtype=jnp.float32)
    h = (x.astype(jnp.float32) * a.astype(jnp.float32)) @ c
    h = h * d.astype(jnp.float32)
    if bias is not None:
        h = h + bias.astype(jnp.float32)
    y = h @ c.T
    return y.astype(x.dtype)


def acdc_bwd_ref(
    x: jax.Array,
    a: jax.Array,
    d: jax.Array,
    g: jax.Array,
):
    """Oracle for the fused backward (paper eqs. 10-14), pure jnp fp32.

    Returns ``(dx, da, dd, dbias)`` — the same contract as
    ``kernels.acdc_bwd``: ``dx`` in ``x.dtype``, diagonal grads fp32.
    This is the four-matmul formulation the Pallas kernel replaced; it
    stays here purely as the test oracle.
    """
    n = x.shape[-1]
    c = transforms.dct_matrix(n, dtype=jnp.float32)
    x2 = x.reshape(-1, n).astype(jnp.float32)
    g2 = g.reshape(-1, n).astype(jnp.float32)
    gc = g2 @ c
    h2 = (x2 * a.astype(jnp.float32)) @ c
    dd = jnp.sum(h2 * gc, axis=0)
    dbias = jnp.sum(gc, axis=0)
    dh1 = (gc * d.astype(jnp.float32)) @ c.T
    da = jnp.sum(x2 * dh1, axis=0)
    dx = (a.astype(jnp.float32) * dh1).astype(x.dtype).reshape(x.shape)
    return dx, da, dd, dbias


def scaled_matmul_ref(
    x: jax.Array,
    w: jax.Array,
    pre: Optional[jax.Array] = None,
    post: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Oracle for the blocked scaled matmul: ``y = ((x*pre) @ w) * post + bias``."""
    h = x.astype(jnp.float32)
    if pre is not None:
        h = h * pre.astype(jnp.float32)
    y = h @ w.astype(jnp.float32)
    if post is not None:
        y = y * post.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def paged_attention_ref(q, knew, vnew, k_pages, v_pages, tbl, pos, window,
                        softcap):
    """Oracle for the fused paged-attention kernel: the gather path of
    ``models/attention.py`` in fp32 at full matmul precision.  Scatter
    the T new tokens into their tail pages, materialise the
    ``(B, MB*bs, Hkv, Dh)`` view through the table, mask causally and by
    window, soft-capped SDPA.  Returns ``(out, k_pages, v_pages)``."""
    b, t, hq, dh = q.shape
    hkv = knew.shape[2]
    n_pages, bs = k_pages.shape[0], k_pages.shape[1]
    mb = tbl.shape[1]
    virtual = mb * bs
    qpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    blk = jnp.minimum(qpos // bs, mb - 1)
    phys = jnp.take_along_axis(tbl, blk, axis=1)
    writable = jnp.logical_and(phys >= 0, qpos < virtual)
    phys = jnp.where(writable, phys, n_pages - 1)
    off = qpos % bs
    k_pages = k_pages.at[phys, off].set(knew.astype(k_pages.dtype))
    v_pages = v_pages.at[phys, off].set(vnew.astype(v_pages.dtype))
    rt = jnp.where(tbl >= 0, tbl, 0)
    ck = k_pages[rt].reshape(b, virtual, hkv, dh)
    cv = v_pages[rt].reshape(b, virtual, hkv, dh)
    kpos = jnp.arange(virtual, dtype=jnp.int32)[None, :]
    causal = kpos[:, None, :] <= qpos[:, :, None]
    inw = jnp.where(window > 0,
                    qpos[:, :, None] - kpos[:, None, :] < window, True)
    mask = jnp.logical_and(causal, inw)
    group = hq // hkv
    hi = jax.lax.Precision.HIGHEST
    qg = q.reshape(b, t, hkv, group, dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck.astype(jnp.float32),
                   precision=hi) * dh**-0.5
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv.astype(jnp.float32),
                   precision=hi)
    return o.reshape(b, t, hq, dh).astype(q.dtype), k_pages, v_pages
