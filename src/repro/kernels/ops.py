"""Public jit'd wrappers around the Pallas kernels.

``acdc_fused`` is the production entry point used by the model zoo when
``method='pallas'``:

* N <= MAX_FUSED_N      -> single fused kernel (paper's "single call");
* larger N, DCT family, -> the factored-DCT kernel (``acdc_factored.py``):
  N % 128 == 0             one call, no N x N operand — the served path;
* any other larger N    -> two chained ``scaled_matmul`` kernels with the
                           diagonals fused (paper's "multiple call");
* custom VJP that RECOMPUTES the transform-domain intermediate ``h2`` in
  the backward pass instead of storing it — the paper's section 5.3
  memory/runtime trade, expressed as a custom_vjp.  The backward itself
  is the fused Pallas kernel in ``acdc_bwd.py`` (one pass per row-block,
  diagonal grads accumulated in VMEM scratch); above ``MAX_FUSED_N`` it
  degrades to chained ``scaled_matmul`` kernels, never to bare XLA
  matmuls.

``acdc_cascade_op`` is the order-K entry point: the whole cascade —
including the interleaved ReLU and riffle permutation of the CaffeNet
configuration — runs as ONE Pallas kernel (``acdc_cascade_fused.py``)
moving 8N bytes per row instead of 8KN, behind a cascade-level custom
VJP.  The primary backward is the reverse-sweep kernel
(``acdc_cascade_bwd.py``): one Pallas call walking all K layers in
reverse with the cotangent resident in VMEM and layer inputs recomputed
on-chip — 12N bytes/row independent of K.  When its VMEM budget (which
includes a (K-1)-deep activation stash) doesn't fit, the backward falls
back to the per-layer HBM-remat scan; when the whole cascade exceeds
the forward fused budget both directions fall back to the per-layer
scan (each layer still fused forward + backward).  Routing decisions
are counted in ``CASCADE_BWD_DISPATCHES`` (cascade backward) and
``ACDC_FWD_DISPATCHES`` (per-layer forward) for the bench/CI regression
gate.

The backward formulas are the paper's eqs. (10)-(14):

    dL/dbias = sum_rows (g C)
    dL/dd    = sum_rows h2 * (g C),      h2 = (x*a) C   (recomputed)
    dL/da    = sum_rows x * ((g C * d) C^T)
    dL/dx    = a * ((g C * d) C^T)

Every op takes a ``family`` argument (static, default ``'acdc'``)
selecting the transform from :mod:`repro.core.families`: the kernels
only require ``C`` real orthonormal with ``C^-1 = C^T`` — true for the
DCT-II, the real-DFT basis (``'circulant'``) and the normalized
Walsh-Hadamard (``'hadamard'``) — so one kernel body serves the whole
zoo; the family supplies the ``C``/``C^T`` operands, the mid-cascade
permuted-columns fold, and the autotune cache key.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import families as families_mod
from repro.core import transforms
from repro.kernels import acdc_bwd as bwd_mod
from repro.kernels import acdc_cascade_bwd as cascade_bwd_mod
from repro.kernels import acdc_cascade_fused as cascade_mod
from repro.kernels import acdc_factored as factored_mod
from repro.kernels import acdc_fused as fused_mod
from repro.kernels import autotune
from repro.kernels import paged_attn as paged_attn_mod
from repro.kernels import scaled_matmul as smm_mod
from repro.obs.metrics import REGISTRY, CounterDict


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode: everywhere but
    on a TPU.  Asked at trace time by every kernel call site, so a test
    can steer it (monkeypatch) and a process's answer follows the backend
    it actually has rather than the one it had at import."""
    return jax.default_backend() != "tpu"


#: trace-time routing decisions of the cascade backward, for benches/CI:
#: every time a cascade VJP backward is traced, exactly one bucket
#: increments.  ``reverse_sweep`` is the fused O(1)-in-K kernel;
#: ``per_layer_scan`` the HBM-remat fallback.  (Counts tracings, not
#: dispatches — a jit cache hit re-runs the kernel without retracing.)
#: The historical dict names remain the canonical mutation surface, but
#: since PR 10 they are shims over labeled counters in the process-
#: global obs registry — ``kernel_cascade_bwd_dispatches_total{route=}``
#: — so serving exporters report them alongside engine metrics.
CASCADE_BWD_DISPATCHES = CounterDict(
    REGISTRY.counter("kernel_cascade_bwd_dispatches_total",
                     "trace-time cascade-backward routing decisions",
                     labels=("route",)),
    ("reverse_sweep", "per_layer_scan"))

#: trace-time routing of the per-layer ACDC forward, same contract as
#: ``CASCADE_BWD_DISPATCHES``: ``fused`` is the single-call kernel at N <=
#: ``MAX_FUSED_N``; ``factored`` the factored-DCT kernel
#: (``acdc_factored.py``) above it for the ``acdc`` family at N a multiple
#: of 128; ``two_call`` the chained ``scaled_matmul`` kernels every other
#: family and size takes above it.  Registry metric:
#: ``kernel_acdc_fwd_dispatches_total{route=}``.
ACDC_FWD_DISPATCHES = CounterDict(
    REGISTRY.counter("kernel_acdc_fwd_dispatches_total",
                     "trace-time per-layer ACDC forward routing decisions",
                     labels=("route",)),
    ("fused", "factored", "two_call"))

#: trace-time routing of the paged-attention decode/verify step, same
#: contract as ``CASCADE_BWD_DISPATCHES``: ``fused`` is the block-table
#: streaming kernel (``paged_attn.py``), ``gather`` the materialized
#: ``k_pages[tbl]`` fallback kept for over-budget shapes and CPU
#: interpret runs.  Registry metric:
#: ``kernel_paged_attn_dispatches_total{route=}``.
PAGED_ATTN_DISPATCHES = CounterDict(
    REGISTRY.counter("kernel_paged_attn_dispatches_total",
                     "trace-time paged-attention routing decisions",
                     labels=("route",)),
    ("fused", "gather"))


def paged_attn_route(hkv: int, dh: int, group: int, t: int, bs: int,
                     dtype) -> Optional[tuple]:
    """Trace-time dispatch for the paged-attention kernel.

    Returns the ``(page_chunk, head_block)`` pair to run the fused
    kernel with, or None to keep the gather fallback.  Policy mirrors
    the cascade kernels: fused on real devices when a block fits the
    per-chunk VMEM budget (block sizes from the autotune ``paged_attn``
    direction, clamped to the call site's head count), gather on CPU
    interpret runs — unless ``paged_attn.FORCE_FUSED`` is set, which
    parity tests and benches use to drive the kernel in interpret mode.
    Every trace increments exactly one ``PAGED_ATTN_DISPATCHES`` bucket.
    """
    itemsize = jnp.dtype(dtype).itemsize
    if interpret_mode() and not paged_attn_mod.FORCE_FUSED:
        PAGED_ATTN_DISPATCHES["gather"] += 1
        return None
    enc = autotune.autotuned_bm("paged_attn", dh, t, dtype)
    blk = paged_attn_mod.clamp_block(
        paged_attn_mod.decode_block(enc), hkv=hkv, dh=dh, group=group,
        t=t, bs=bs, itemsize=itemsize)
    if blk is None:
        PAGED_ATTN_DISPATCHES["gather"] += 1
        return None
    PAGED_ATTN_DISPATCHES["fused"] += 1
    return blk


def _flatten(x):
    return x.reshape(-1, x.shape[-1]), x.shape


def _family_mats(family, n):
    """The family's fp32 ``(C, C^T)`` kernel operand pair at size ``n``."""
    return families_mod.get_family(family).matrices(n, jnp.float32)


def _acdc_fwd_impl(x2, a, d, bias, *, family="acdc", interpret):
    n = x2.shape[-1]
    if n <= fused_mod.MAX_FUSED_N:
        ACDC_FWD_DISPATCHES["fused"] += 1
        c, ct = _family_mats(family, n)
        bm = autotune.autotuned_bm("fwd", n, dtype=x2.dtype,
                                   bias=bias is not None, family=family)
        return fused_mod.acdc_fused_pallas(x2, a, d, bias, c, ct, bm=bm,
                                           interpret=interpret)
    if family == "acdc" and factored_mod.supports(n):
        # The DCT factored into VMEM-resident stages: no N x N operand.
        ACDC_FWD_DISPATCHES["factored"] += 1
        bm = factored_mod.pick_bm(x2.shape[0], n, x2.dtype.itemsize)
        return factored_mod.acdc_factored_pallas(x2, a, d, bias, bm=bm,
                                                 interpret=interpret)
    ACDC_FWD_DISPATCHES["two_call"] += 1
    c, ct = _family_mats(family, n)
    # Two-call path: h2 lands in HBM exactly once.  A and D are fused as
    # pre-scales; the bias-on-D commutes through the final matmul as
    # bias @ C^T (an O(N^2) one-off, amortized over the batch).
    h2 = smm_mod.scaled_matmul_pallas(x2, c, pre=a, interpret=interpret)
    bias_t = None
    if bias is not None:
        bias_t = (bias.astype(jnp.float32) @ ct).astype(x2.dtype)
    return smm_mod.scaled_matmul_pallas(h2, ct, pre=d, bias=bias_t,
                                        interpret=interpret)


def _acdc_bwd_impl(x2, a, d, g2, *, family="acdc", with_bias=True,
                   interpret):
    """Pallas backward dispatch; returns (dx2, da, dd, dbias), diagonal
    grads in fp32 (the VMEM accumulator precision).  ``with_bias=False``
    skips the dbias reduction entirely (dbias comes back ``None``)."""
    n = x2.shape[-1]
    c, ct = _family_mats(family, n)
    if n <= fused_mod.MAX_FUSED_N:
        bm = autotune.autotuned_bm("bwd", n, dtype=x2.dtype,
                                   bias=with_bias, family=family)
        return bwd_mod.acdc_bwd_pallas(x2, g2, a, d, c, ct,
                                       with_bias=with_bias, bm=bm,
                                       interpret=interpret)
    return bwd_mod.acdc_bwd_two_call(x2, g2, a, d, c, ct,
                                     with_bias=with_bias,
                                     interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_bias(family, x, a, d, bias):
    x2, shape = _flatten(x)
    y = _acdc_fwd_impl(x2, a, d, bias, family=family,
                       interpret=interpret_mode())
    return y.reshape(shape)


def _fused_bias_fwd(family, x, a, d, bias):
    return _fused_bias(family, x, a, d, bias), (x, a, d, bias)


def _fused_bias_bwd(family, res, g):
    x, a, d, bias = res
    x2, shape = _flatten(x)
    g2, _ = _flatten(g)
    dx2, da, dd, db = _acdc_bwd_impl(x2, a, d, g2, family=family,
                                     interpret=interpret_mode())
    return (dx2.reshape(shape), da.astype(a.dtype), dd.astype(d.dtype),
            db.astype(bias.dtype))


_fused_bias.defvjp(_fused_bias_fwd, _fused_bias_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_nobias(family, x, a, d):
    x2, shape = _flatten(x)
    y = _acdc_fwd_impl(x2, a, d, None, family=family,
                       interpret=interpret_mode())
    return y.reshape(shape)


def _fused_nobias_fwd(family, x, a, d):
    return _fused_nobias(family, x, a, d), (x, a, d)


def _fused_nobias_bwd(family, res, g):
    x, a, d = res
    x2, shape = _flatten(x)
    g2, _ = _flatten(g)
    dx2, da, dd, _ = _acdc_bwd_impl(x2, a, d, g2, family=family,
                                    with_bias=False,
                                    interpret=interpret_mode())
    return dx2.reshape(shape), da.astype(a.dtype), dd.astype(d.dtype)


_fused_nobias.defvjp(_fused_nobias_fwd, _fused_nobias_bwd)


def acdc_fused(x, a, d, bias, family="acdc"):
    """Fused layer ``y = ((x*a) C * d + bias) C^T`` along the last axis;
    ``C`` from the transform family registry."""
    return _fused_bias(family, x, a, d, bias)


def acdc_fused_nobias(x, a, d, family="acdc"):
    """Bias-free fused layer: ``y = ((x*a) C * d) C^T``.

    A separate primitive (not ``acdc_fused`` with zeros): the LM path sets
    ``bias=False`` on every projection, and a dummy zero bias would pay the
    broadcast add in the forward AND a full (M, N) reduction for its VJP on
    every call.
    """
    return _fused_nobias(family, x, a, d)


def acdc_fused_op(
    x: jax.Array,
    a: jax.Array,
    d: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    family: str = "acdc",
) -> jax.Array:
    """User-facing fused layer; dispatches on the optional bias."""
    if bias is None:
        return _fused_nobias(family, x, a, d)
    return _fused_bias(family, x, a, d, bias)


# ---------------------------------------------------------------------------
# Order-K cascade: whole-cascade fusion + cascade-level custom VJP.
# ---------------------------------------------------------------------------

def _cascade_fwd_impl(x2, a, d, bias, relu, permute, family, *, interpret):
    n = x2.shape[-1]
    fam = families_mod.get_family(family)
    c, ct = fam.matrices(n, jnp.float32)
    ct_mid = None
    if permute:
        # Fold the riffle into the mid-cascade inverse transform:
        # (z @ C^T)[:, p] == z @ C^T[:, p] — no in-kernel gather.
        ct_mid = ct[:, fam.riffle(n)]
    # Row block autotuned within the VMEM budget left by the transform
    # matrices (fixed pick_bm answer off-device); the dispatcher
    # guaranteed some block fits before routing here.
    bm = autotune.autotuned_bm("cascade", n, a.shape[0], x2.dtype,
                               bias=bias is not None, permute=permute,
                               family=family)
    # named_scope costs only at trace time: it labels the jaxpr/HLO so
    # profiler captures show the cascade as one named row
    with jax.named_scope("acdc_cascade_fwd"):
        return cascade_mod.acdc_cascade_pallas(x2, a, d, bias, c, ct,
                                               ct_mid, relu=relu, bm=bm,
                                               interpret=interpret)


def _cascade_bwd_fused(relu, permute, x, a, d, bias, g, family="acdc"):
    """Reverse-sweep cascade backward: ONE Pallas kernel walks all K
    layers in reverse with the cotangent resident in VMEM, recomputing
    layer inputs on-chip (``acdc_cascade_bwd.py``) — 12N HBM bytes/row
    independent of K, symmetric with the fused forward."""
    n = x.shape[-1]
    k = a.shape[0]
    x2, shape = _flatten(x)
    g2, _ = _flatten(g)
    fam = families_mod.get_family(family)
    c, ct = fam.matrices(n, jnp.float32)
    ct_mid = ct[:, fam.riffle(n)] if permute else None
    bm = autotune.autotuned_bm("cascade_bwd", n, k, x2.dtype,
                               bias=bias is not None, permute=permute,
                               family=family)
    with jax.named_scope("acdc_cascade_bwd_reverse_sweep"):
        dx, da, dd, db = cascade_bwd_mod.acdc_cascade_bwd_pallas(
            x2, g2, a, d, bias, c, ct, ct_mid, relu=relu, bm=bm,
            interpret=interpret_mode())
    dx = dx.reshape(shape)
    if bias is None:
        return dx, da.astype(a.dtype), dd.astype(d.dtype)
    return (dx, da.astype(a.dtype), dd.astype(d.dtype),
            db.astype(bias.dtype))


def _cascade_bwd_dispatch(relu, permute, family, x, a, d, bias, g):
    """Primary VJP routing: reverse-sweep kernel when its (deeper) VMEM
    budget fits, else the per-layer HBM-remat scan.  The budgets differ —
    the backward stashes (K-1) row blocks — so a cascade can run fused
    forward and still fall back here."""
    n = x.shape[-1]
    k = a.shape[0]
    if cascade_bwd_mod.fits_vmem(n, k, permute=permute,
                                 bias=bias is not None):
        CASCADE_BWD_DISPATCHES["reverse_sweep"] += 1
        return _cascade_bwd_fused(relu, permute, x, a, d, bias, g,
                                  family=family)
    CASCADE_BWD_DISPATCHES["per_layer_scan"] += 1
    return _cascade_bwd_core(relu, permute, x, a, d, bias, g,
                             family=family)


def _cascade_bwd_core(relu, permute, x, a, d, bias, g, family="acdc"):
    """Cascade backward fallback: recompute per-layer inputs to HBM
    (section 5.3 trade at cascade scope — the fused forward stores
    NOTHING but x), then run the fused per-layer backward kernel in
    reverse under ``lax.scan``.  O(KN) bytes/row; used only when the
    reverse-sweep kernel's VMEM budget doesn't fit."""
    n = x.shape[-1]
    x2, shape = _flatten(x)
    g2, _ = _flatten(g)
    interp = interpret_mode()
    perm = inv_perm = None
    if permute:
        p = families_mod.get_family(family).riffle(n)
        perm = jnp.asarray(p)
        inv_perm = jnp.asarray(transforms.invert_permutation(p))

    with_bias = bias is not None
    layers = {"a": a, "d": d}
    if with_bias:
        layers["bias"] = bias

    def fstep(h, layer):
        z = _acdc_fwd_impl(h, layer["a"], layer["d"], layer.get("bias"),
                           family=family, interpret=interp)
        hn = jnp.maximum(z, 0) if relu else z
        if perm is not None:
            hn = hn[:, perm]
        # the z residual exists only to rebuild the ReLU mask — don't
        # stack a (K-1, M, N) tensor in HBM for linear cascades.
        return hn, (h, z) if relu else h

    # Recompute only the K-1 interleaved layers: hs[i] is the input to
    # layer i, zs[i] its pre-interleave output, and the final carry is
    # the last layer's input (its own forward output is never needed).
    head = jax.tree.map(lambda p: p[:-1], layers)
    if relu:
        h_last, (hs, zs) = jax.lax.scan(fstep, x2, head)
    else:
        h_last, hs = jax.lax.scan(fstep, x2, head)

    # Last layer: the upstream cotangent applies directly (no interleave
    # after the final layer).
    dh, da_k, dd_k, db_k = _acdc_bwd_impl(h_last, a[-1], d[-1], g2,
                                          family=family,
                                          with_bias=with_bias,
                                          interpret=interp)

    def bstep(gcur, inp):
        if relu:
            h_i, z_i, layer = inp
        else:
            h_i, layer = inp
        gz = gcur[:, inv_perm] if inv_perm is not None else gcur
        if relu:
            gz = jnp.where(z_i > 0, gz, jnp.zeros_like(gz))
        dx, da_i, dd_i, db_i = _acdc_bwd_impl(h_i, layer["a"], layer["d"],
                                              gz, family=family,
                                              with_bias=with_bias,
                                              interpret=interp)
        return dx, (da_i, dd_i, db_i)

    xs = (hs, zs, head) if relu else (hs, head)
    dh, (das, dds, dbs) = jax.lax.scan(bstep, dh, xs, reverse=True)

    da = jnp.concatenate([das, da_k[None]], axis=0).astype(a.dtype)
    dd = jnp.concatenate([dds, dd_k[None]], axis=0).astype(d.dtype)
    dx = dh.reshape(shape)
    if bias is None:
        return dx, da, dd
    db = jnp.concatenate([dbs, db_k[None]], axis=0).astype(bias.dtype)
    return dx, da, dd, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _cascade_bias(relu, permute, family, x, a, d, bias):
    x2, shape = _flatten(x)
    y = _cascade_fwd_impl(x2, a, d, bias, relu, permute, family,
                          interpret=interpret_mode())
    return y.reshape(shape)


def _cascade_bias_fwd(relu, permute, family, x, a, d, bias):
    return (_cascade_bias(relu, permute, family, x, a, d, bias),
            (x, a, d, bias))


def _cascade_bias_bwd(relu, permute, family, res, g):
    x, a, d, bias = res
    return _cascade_bwd_dispatch(relu, permute, family, x, a, d, bias, g)


_cascade_bias.defvjp(_cascade_bias_fwd, _cascade_bias_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _cascade_nobias(relu, permute, family, x, a, d):
    x2, shape = _flatten(x)
    y = _cascade_fwd_impl(x2, a, d, None, relu, permute, family,
                          interpret=interpret_mode())
    return y.reshape(shape)


def _cascade_nobias_fwd(relu, permute, family, x, a, d):
    return _cascade_nobias(relu, permute, family, x, a, d), (x, a, d)


def _cascade_nobias_bwd(relu, permute, family, res, g):
    x, a, d = res
    return _cascade_bwd_dispatch(relu, permute, family, x, a, d, None, g)


_cascade_nobias.defvjp(_cascade_nobias_fwd, _cascade_nobias_bwd)


def _cascade_per_layer(x, a, d, bias, relu, permute, family="acdc"):
    """Fallback when the whole cascade exceeds the fused VMEM budget:
    ``lax.scan`` over per-layer fused ops (8KN bytes/row, each layer still
    a fused forward + fused backward)."""
    n = x.shape[-1]
    fam = families_mod.get_family(family)
    perm = jnp.asarray(fam.riffle(n)) if permute else None
    layers = {"a": a, "d": d}
    if bias is not None:
        layers["bias"] = bias

    def body(h, layer):
        y = acdc_fused_op(h, layer["a"], layer["d"], layer.get("bias"),
                          family=family)
        if relu:
            y = jax.nn.relu(y)
        if perm is not None:
            y = y[..., perm]
        return y, None

    head = jax.tree.map(lambda p: p[:-1], layers)
    last = jax.tree.map(lambda p: p[-1], layers)
    h, _ = jax.lax.scan(body, x, head)
    return acdc_fused_op(h, last["a"], last["d"], last.get("bias"),
                         family=family)


def acdc_cascade_op(
    x: jax.Array,
    a: jax.Array,
    d: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    relu: bool = False,
    permute: bool = False,
    family: str = "acdc",
) -> jax.Array:
    """Order-K fused cascade: stacked (K, N) diagonals, one kernel.

    Dispatch: K == 1 degenerates to the single-layer op; cascades that fit
    the fused kernel's VMEM budget run whole-cascade fused (8N bytes/row,
    independent of K) behind the cascade-level custom VJP; anything larger
    falls back to the per-layer scan.  ``family`` picks the transform
    (static — one compiled program per family).
    """
    k = a.shape[0]
    if k == 1:
        return acdc_fused_op(x, a[0], d[0],
                             None if bias is None else bias[0],
                             family=family)
    n = x.shape[-1]
    if not cascade_mod.fits_vmem(n, k, permute=permute,
                                 bias=bias is not None):
        return _cascade_per_layer(x, a, d, bias, relu, permute, family)
    if bias is None:
        return _cascade_nobias(relu, permute, family, x, a, d)
    return _cascade_bias(relu, permute, family, x, a, d, bias)


def scaled_matmul(
    x: jax.Array,
    w: jax.Array,
    pre: Optional[jax.Array] = None,
    post: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Blocked scaled matmul on the last axis of ``x``."""
    x2, shape = _flatten(x)
    y = smm_mod.scaled_matmul_pallas(x2, w, pre, post, bias,
                                     interpret=interpret_mode())
    return y.reshape(*shape[:-1], w.shape[-1])
