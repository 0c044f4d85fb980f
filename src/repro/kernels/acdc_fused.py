"""Fused ACDC Pallas TPU kernel — the "single call" implementation.

TPU adaptation of the paper's section 5.1 fused CUDA kernel.  The GPU
version fuses A-scale -> DCT -> D-scale -> IDCT into one kernel, keeping
intermediates in shared memory so only 8N bytes move through HBM per row.
The TPU version keeps the same fusion structure but replaces the butterfly
DCT with MXU matmuls against the precomputed orthonormal DCT matrix
(DESIGN.md section 3): butterflies are VPU-shaped; the MXU wants 128x128
systolic matmuls.

Memory behaviour per grid step (row-block of ``bm`` rows):

    HBM reads : x tile (bm x N) + C tiles (N x N, reused across the grid and
                therefore cached/streamed once for the whole batch)
    VMEM      : h1, h2, h3 intermediates — never touch HBM
    HBM write : y tile (bm x N)

which is exactly the paper's "minimum 8N bytes moved per layer" once the
transform matrix is amortized over a large batch.  Like the paper's fused
kernel, this path is limited by on-chip memory: both C and C^T tiles must
fit VMEM, so it is used for N <= ``MAX_FUSED_N``; larger sizes take the
factored-DCT kernel (``acdc_factored.py``) or the two-call
``scaled_matmul`` path (ops.py picks automatically).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# fp32 C + C^T at N=2048 -> 2 * 16MB exceeds VMEM (~16MB/core on v5e).
# N=1024 -> 2 * 4MB + tiles: fits comfortably.
#
# The whole-cascade fused kernel (acdc_cascade_fused.py) shares this gate
# and adds to the same budget: K stacked (K, N) diagonals (a, d, bias ->
# up to 12 KB * K at N=1024, negligible) and, when riffling, a THIRD N^2
# matrix (the column-permuted C^T for mid-cascade layers) -> ~12 MB of
# matrices at N=1024.  ``acdc_cascade_fused.fits_vmem`` does the exact
# arithmetic and ops.py falls back to the per-layer scan when it fails.
MAX_FUSED_N = 1024
DEFAULT_BM = 256


def _acdc_kernel(x_ref, a_ref, d_ref, bias_ref, c_ref, ct_ref, o_ref):
    """One row-block: y = ((x*a) @ C * d + bias) @ C^T, all in VMEM."""
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    h1 = x * a  # (bm, N) * (1, N)
    h2 = jnp.dot(h1, c_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    h3 = h2 * d
    if bias_ref is not None:
        h3 = h3 + bias_ref[...].astype(jnp.float32)
    y = jnp.dot(h3, ct_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def acdc_fused_pallas(
    x: jax.Array,
    a: jax.Array,
    d: jax.Array,
    bias: Optional[jax.Array],
    c: jax.Array,
    ct: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    interpret: bool = False,
) -> jax.Array:
    """Fused ACDC over a 2-D ``x`` of shape (M, N).  N must be <= MAX_FUSED_N
    and a multiple of 128 for the MXU; M is padded to ``bm`` internally.
    """
    m, n = x.shape
    bm = min(bm, max(8, m))
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    grid = (x.shape[0] // bm,)

    a2 = a.reshape(1, n)
    d2 = d.reshape(1, n)
    bias2 = bias.reshape(1, n) if bias is not None else None

    diag_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    mat_spec = pl.BlockSpec((n, n), lambda i: (0, 0))
    row_spec = pl.BlockSpec((bm, n), lambda i: (i, 0))

    kernel = _acdc_kernel
    operands = [x, a2, d2]
    in_specs = [row_spec, diag_spec, diag_spec]
    if bias2 is not None:
        operands.append(bias2)
        in_specs.append(diag_spec)
    else:
        kernel = functools.partial(_no_bias_kernel, _acdc_kernel)
    operands += [c, ct]
    in_specs += [mat_spec, mat_spec]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], n), x.dtype),
        interpret=interpret,
    )(*operands)
    if pad_m:
        out = out[:m]
    return out


def _no_bias_kernel(inner, x_ref, a_ref, d_ref, c_ref, ct_ref, o_ref):
    inner(x_ref, a_ref, d_ref, None, c_ref, ct_ref, o_ref)
