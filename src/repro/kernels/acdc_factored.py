"""Factored-DCT ACDC forward for N above ``MAX_FUSED_N`` — one Pallas call.

Above ``MAX_FUSED_N`` the dense transform matrices no longer fit VMEM, and
streaming an N x N fp32 ``C`` (then ``C^T``) from HBM for every row block
is what the two-call ``scaled_matmul`` path pays: 2 * 4 N^2 bytes a layer,
which at N = 6144 is 0.3 GB.  The DCT-II needs none of it.  With
``N = N1 * 128`` it splits, four-step FFT style, into two small complex
matmuls around a twiddle diagonal whose operands stay resident in VMEM:

* input index ``n = 128 n1 + n2`` — a row reshapes to (N1, 128) as stored;
* output index ``k = N1 k2 + k1`` with ``q = k2 mod 2``;
* stage 1 (contract n1): ``Y[k1', n2] = sum_n1 x[n1, n2] G[n1, k1']`` with
  ``G = exp(i pi n1 k1' / N1)`` and ``k1' < 2 N1`` (the ``(-1)^(n1 k2)``
  of the odd ``k2`` folds into the upper half of ``k1'``);
* twiddle: ``W_q[k1, n2] = Y[k1 + q N1, n2] T[k1, n2]`` with
  ``T = exp(i pi k1 (2 n2 + 1) / 2N)``;
* stage 2 (contract n2): ``X[k] = s_k Re sum_n2 W_q[k1, n2] H[n2, k2]``
  with ``H = exp(i pi (2 n2 + 1) k2 / 256)``.

The inverse (DCT-III, ``C^T``) is the transpose of the same three stages
in reverse order.  The transform-domain row never takes natural order: it
stays as (N1, 128) with lane ``j = 64 q + k2 // 2`` (:func:`layout_perm`),
and the wrapper gathers ``d`` and ``bias`` into that layout instead (O(N)).

Per row block of ``bm`` rows, all in fp32 VMEM: stage 1 and its transpose
contract the second-minor axis, so they run per row, ``STAGE1_ROWS`` rows
side by side in lanes to a dot (``fori_loop``); the stage-2 matmuls
contract lanes and run once over all ``bm * N1`` rows.
HBM traffic is x in and y out; the operands (G, T, H blocks) are under
1 MB at N = 6144 and the work about ``N (8 N1 + 8 * 128)`` multiply-adds a
row per transform instead of ``N^2``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HALF = LANES // 2

# Scoped VMEM the row block may fill, and the limit the call asks for
# (v5e has 128 MiB of VMEM a core; the compiler's default scope is 16).
VMEM_BUDGET = 40 * 1024 * 1024
VMEM_LIMIT = 64 * 1024 * 1024
BM_CHOICES = (256, 128, 64, 32, 16, 8)
# Rows that share one stage-1 dot, side by side in lanes.  One v5e, N
# 6144: 26.5 / 20.0 us a layer at 32 rows for 1 / 32, 384 / 184 us at 1024.
STAGE1_ROWS = 32


def supports(n: int) -> bool:
    """Whether the factored kernel covers size ``n`` (any multiple of 128)."""
    return n % LANES == 0


@functools.lru_cache(maxsize=16)
def stage_operands(n: int):
    """float64 stage operands at size ``n``: ``gf`` (4 N1, N1), ``gi`` (N1,
    4 N1), twiddle ``tr``/``ti`` (N1, 128), ``hs`` (512, 128), ``ht``
    (128, 512); the kernel takes them in fp32."""
    n1 = n // LANES
    r1 = np.arange(n1)
    n2 = np.arange(LANES)
    g = np.exp(1j * np.pi * np.outer(r1, np.arange(2 * n1)) / n1)
    t = np.exp(1j * np.pi * np.outer(r1, 2 * n2 + 1) / (2 * n))
    h = np.exp(1j * np.pi * np.outer(2 * n2 + 1, n2) / (2 * LANES))
    gq = (g[:, :n1], g[:, n1:])
    # stage 1, forward: rows of Y in blocks [Re q0; Im q0; Re q1; Im q1]
    gf = np.concatenate([gq[0].real.T, gq[0].imag.T,
                         gq[1].real.T, gq[1].imag.T], axis=0)
    # stage 1, inverse: y = sum_q Re G_q . U_q over the same blocks
    gi = np.concatenate([gq[0].real, -gq[0].imag,
                         gq[1].real, -gq[1].imag], axis=1)
    # stage 2, forward: lanes of W in blocks [Re q0 | Im q0 | Re q1 | Im q1]
    # -> lane j = 64 q + k2 // 2 of the transform-domain row; the sqrt(2/N)
    # of the orthonormal scale rides here (s_0's extra 1/sqrt(2) in-kernel)
    j = np.arange(LANES)
    q = j // HALF
    k2 = 2 * (j % HALF) + q
    hs = np.zeros((4, LANES, LANES))
    for p in range(2):
        cols = q == p
        hs[2 * p][:, cols] = h.real[:, k2[cols]]
        hs[2 * p + 1][:, cols] = -h.imag[:, k2[cols]]
    hs *= np.sqrt(2.0 / n)
    ht = np.concatenate([hs[0].T, -hs[1].T, hs[2].T, -hs[3].T], axis=1)
    return gf, gi, t.real, t.imag, hs.reshape(4 * LANES, LANES), ht


@functools.lru_cache(maxsize=16)
def layout_perm(n: int) -> np.ndarray:
    """(N1, 128) frequency index held at each place of the transform-domain
    row: ``perm[k1, j] = N1 (2 (j mod 64) + j // 64) + k1``."""
    n1 = n // LANES
    j = np.arange(LANES)
    k2 = 2 * (j % HALF) + j // HALF
    return n1 * k2[None, :] + np.arange(n1)[:, None]


def to_layout(v: jax.Array) -> jax.Array:
    """A length-N frequency-domain vector in the transform-domain layout,
    ``v[layout_perm(n)]``, by a reshape and a transpose (a gather of N
    elements costs tens of microseconds on a TPU)."""
    n1 = v.shape[-1] // LANES
    return v.reshape(HALF, 2, n1).transpose(2, 1, 0).reshape(n1, LANES)


def vmem_bytes(n: int, bm: int, itemsize: int) -> int:
    """Modelled VMEM of one grid step: double-buffered x and y tiles, the
    fp32 scratch (x, W and U at 4N, y) and the live stage-2 values (Z at N,
    V at 4N), the live values of one stage-1 dot (its input, output and
    twiddled output over ``STAGE1_ROWS`` rows: ~10N each), and the
    double-buffered operands (< 1 MB at N = 6144)."""
    n1 = n // LANES
    tiles = 2 * 2 * bm * n * itemsize
    f32_rows = 4 * bm * n * (1 + 4 + 4 + 1 + 1 + 4)
    stage1 = 4 * math.gcd(STAGE1_ROWS, bm) * n * 10
    consts = 2 * 4 * (2 * 4 * n1 * n1 + 2 * n + 2 * 4 * LANES * LANES + 3 * n)
    return tiles + f32_rows + stage1 + consts


def pick_bm(m: int, n: int, itemsize: int) -> int:
    """Fixed row block at (M, N): all of M (rounded up to 8) when that fits
    the budget — one block for a decode batch — else the largest of
    ``BM_CHOICES`` that does."""
    whole = max(8, -(-m // 8) * 8)
    for bm in (whole,) + BM_CHOICES:
        if bm <= whole and vmem_bytes(n, bm, itemsize) <= VMEM_BUDGET:
            return bm
    return BM_CHOICES[-1]


def _complex_mul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _factored_kernel(x_ref, a_ref, d_ref, bias_ref, gf_ref, gi_ref, tr_ref,
                     ti_ref, hs_ref, ht_ref, o_ref, x3_s, w_s, u_s, y3_s):
    bm, n = x_ref.shape
    n1 = n // LANES
    f32 = jnp.float32
    x3_s[...] = (x_ref[...].astype(f32).reshape(bm, n1, LANES)
                 * a_ref[...].astype(f32))
    r = math.gcd(STAGE1_ROWS, bm)
    tr = tr_ref[...]
    ti = ti_ref[...]
    tr_r = jnp.concatenate([tr] * r, axis=1)
    ti_r = jnp.concatenate([ti] * r, axis=1)
    gf = gf_ref[...]

    def lanes(blocks):
        return jnp.concatenate([blocks[i] for i in range(r)], axis=1)

    def stage1(c, carry):
        m0 = pl.multiple_of(c * r, r)
        y = jnp.dot(gf, lanes(x3_s[pl.ds(m0, r)]),
                    preferred_element_type=f32)        # (4 N1, r 128)
        w = []
        for p in range(2):
            yr = y[2 * p * n1:(2 * p + 1) * n1]
            yi = y[(2 * p + 1) * n1:(2 * p + 2) * n1]
            w += _complex_mul(yr, yi, tr_r, ti_r)
        for i in range(r):
            w_s[m0 + i] = jnp.concatenate(
                [part[:, i * LANES:(i + 1) * LANES] for part in w], axis=1)
        return carry

    jax.lax.fori_loop(0, bm // r, stage1, 0)
    z = jnp.dot(w_s[...].reshape(bm * n1, 4 * LANES), hs_ref[...],
                preferred_element_type=f32).reshape(bm, n1, LANES)
    # s_0 = sqrt(1/N) where the rest are sqrt(2/N): k = 0 sits at (0, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (n1, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n1, LANES), 1)
    s0 = jnp.where((row == 0) & (lane == 0), f32(np.sqrt(0.5)), f32(1.0))
    z = z * s0 * d_ref[...].astype(f32)
    if bias_ref is not None:
        z = z + bias_ref[...].astype(f32)
    z = z * s0
    v = jnp.dot(z.reshape(bm * n1, LANES), ht_ref[...],
                preferred_element_type=f32).reshape(bm, n1, 4 * LANES)
    u = []
    for p in range(2):
        vr = v[:, :, 2 * p * LANES:(2 * p + 1) * LANES]
        vi = v[:, :, (2 * p + 1) * LANES:(2 * p + 2) * LANES]
        u += _complex_mul(vr, vi, tr, ti)
    u_s[...] = jnp.concatenate(u, axis=1)                       # (bm, 4 N1, 128)
    gi = gi_ref[...]

    def stage1_t(c, carry):
        m0 = pl.multiple_of(c * r, r)
        y = jnp.dot(gi, lanes(u_s[pl.ds(m0, r)]),
                    preferred_element_type=f32)        # (N1, r 128)
        for i in range(r):
            y3_s[m0 + i] = y[:, i * LANES:(i + 1) * LANES]
        return carry

    jax.lax.fori_loop(0, bm // r, stage1_t, 0)
    o_ref[...] = y3_s[...].reshape(bm, n).astype(o_ref.dtype)


def _no_bias_kernel(x_ref, a_ref, d_ref, *rest):
    _factored_kernel(x_ref, a_ref, d_ref, None, *rest)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def acdc_factored_pallas(
    x: jax.Array,
    a: jax.Array,
    d: jax.Array,
    bias: Optional[jax.Array],
    *,
    bm: int,
    interpret: bool = False,
) -> jax.Array:
    """DCT-family ACDC layer ``y = IDCT(d * DCT(a * x) + bias)`` over a 2-D
    ``x`` (M, N), N a multiple of 128, in one call per ``bm`` rows.

    ``a``, ``d`` and ``bias`` go in uncast; every dot takes fp32 operands
    and accumulates in fp32; the result is cast to ``x.dtype`` at the end.
    """
    m, n = x.shape
    n1 = n // LANES
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    operands = [x, a.reshape(n1, LANES), to_layout(d)]
    if bias is not None:
        operands.append(to_layout(bias))
    operands += [jnp.asarray(o, jnp.float32) for o in stage_operands(n)]

    def whole(arr):
        return pl.BlockSpec(arr.shape, lambda i: (0,) * arr.ndim)

    row_spec = pl.BlockSpec((bm, n), lambda i: (i, 0))
    f32 = jnp.float32
    out = pl.pallas_call(
        _factored_kernel if bias is not None else _no_bias_kernel,
        grid=(x.shape[0] // bm,),
        in_specs=[row_spec] + [whole(o) for o in operands[1:]],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, n1, LANES), f32),
                        pltpu.VMEM((bm, n1, 4 * LANES), f32),
                        pltpu.VMEM((bm, 4 * n1, LANES), f32),
                        pltpu.VMEM((bm, n1, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    if pad_m:
        out = out[:m]
    return out
