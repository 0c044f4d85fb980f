"""Factored-DCT ACDC forward (``kernels/acdc_factored.py``) above
``MAX_FUSED_N``: its stages against the float64 DCT matrix, the whole
layer (interpret mode) against the ``kernels/ref`` oracle and against the
two-call path it replaces, and the forward routing counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transforms as T
from repro.kernels import acdc_factored as fac
from repro.kernels import acdc_fused as fused_mod
from repro.kernels import ops, ref
from repro.kernels import scaled_matmul as smm_mod

SIZES = [1152, 2048, 6144]


def _s0(n):
    """The kernel's extra 1/sqrt(2) at k = 0, in the (N1, 128) layout."""
    s = np.ones((n // fac.LANES, fac.LANES))
    s[0, 0] = np.sqrt(0.5)
    return s


def _forward_stages(x, n):
    """The kernel's forward arithmetic in float64: (M, N) natural rows ->
    (M, N1, 128) orthonormal DCT-II in the transform-domain layout."""
    gf, _, tr, ti, hs, _ = fac.stage_operands(n)
    n1 = n // fac.LANES
    y = np.einsum("kn,mnj->mkj", gf, x.reshape(-1, n1, fac.LANES))
    w = []
    for p in range(2):
        yr = y[:, 2 * p * n1:(2 * p + 1) * n1]
        yi = y[:, (2 * p + 1) * n1:(2 * p + 2) * n1]
        w += [yr * tr - yi * ti, yr * ti + yi * tr]
    return (np.concatenate(w, axis=2) @ hs) * _s0(n)


def _inverse_stages(z, n):
    """The kernel's inverse arithmetic in float64: (M, N1, 128) layout ->
    (M, N) natural rows of the orthonormal DCT-III."""
    _, gi, tr, ti, _, ht = fac.stage_operands(n)
    v = (z * _s0(n)) @ ht
    u = []
    for p in range(2):
        vr = v[..., 2 * p * fac.LANES:(2 * p + 1) * fac.LANES]
        vi = v[..., (2 * p + 1) * fac.LANES:(2 * p + 2) * fac.LANES]
        u += [vr * tr - vi * ti, vr * ti + vi * tr]
    y = np.einsum("nk,mkj->mnj", gi, np.concatenate(u, axis=1))
    return y.reshape(z.shape[0], n)


@pytest.mark.parametrize("n", SIZES)
def test_stages_match_the_dct_matrix(n):
    """Forward stages == x C, inverse stages == z C^T (float64), with the
    transform domain read through ``layout_perm``, which ``to_layout``
    (the wrapper's reshape of ``d`` and ``bias``) reproduces."""
    c = T._dct_matrix_np(n)
    perm = fac.layout_perm(n)
    assert sorted(perm.reshape(-1)) == list(range(n))
    np.testing.assert_array_equal(fac.to_layout(np.arange(n)), perm)
    rng = np.random.default_rng(n)
    x = rng.standard_normal((3, n))
    np.testing.assert_allclose(_forward_stages(x, n), (x @ c)[:, perm],
                               atol=1e-10)
    z = rng.standard_normal((3, n))
    np.testing.assert_allclose(_inverse_stages(z[:, perm], n), z @ c.T,
                               atol=1e-10)


def _layer_inputs(shape, dtype, seed):
    n = shape[-1]
    r = jax.random.PRNGKey(seed)
    x = jax.random.normal(r, shape, dtype)
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (n,))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (n,))
    b = 0.1 * jax.random.normal(jax.random.fold_in(r, 3), (n,))
    return x, a, d, b


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_layer_vs_oracle(n, bias, dtype):
    """The dispatched layer (5 rows: not a multiple of the 8-row block)
    against the fp32 dense-matrix oracle."""
    x, a, d, b = _layer_inputs((5, n), dtype, n + bias)
    b = b if bias else None
    got = ops.acdc_fused_op(x, a, d, b)
    want = ref.acdc_fused_ref(x, a, d, b)
    assert got.dtype == x.dtype and got.shape == x.shape
    # bf16: both sides round the output once (half an ulp of ~4 sigma)
    atol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_kernel_pads_rows_to_the_block():
    """M not a multiple of ``bm``: 7 rows in blocks of 8 and 3 rows of 8."""
    n = 2048
    x, a, d, b = _layer_inputs((7, n), jnp.float32, 3)
    got = fac.acdc_factored_pallas(x, a, d, b, bm=8, interpret=True)
    want = ref.acdc_fused_ref(x, a, d, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    got3 = fac.acdc_factored_pallas(x[:3], a, d, b, bm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got3), np.asarray(want[:3]),
                               atol=2e-5)


def test_nd_batch():
    x, a, d, b = _layer_inputs((2, 3, 2048), jnp.float32, 4)
    got = ops.acdc_fused_op(x, a, d, b)
    want = ref.acdc_fused_ref(x, a, d, b)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("n", [2048, 6144])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_no_less_accurate_than_two_call(n, dtype):
    """Against float64, the factored layer's largest error is no larger
    than the two-call path's on the same inputs with served (bf16)
    activations, where the two-call path rounds its intermediate to bf16
    between the calls.  With fp32 activations both sit at fp32 rounding
    (~2e-6 at N 6144): the factored one within 1.5x of it."""
    x, a, d, b = _layer_inputs((4, n), dtype, n)
    c64 = T._dct_matrix_np(n)
    want = ((np.asarray(x, np.float64) * np.asarray(a)) @ c64
            * np.asarray(d) + np.asarray(b)) @ c64.T
    bm = fac.pick_bm(4, n, x.dtype.itemsize)
    got = fac.acdc_factored_pallas(x, a, d, b, bm=bm, interpret=True)
    c, ct = T.dct_matrix(n), T.idct_matrix(n)
    h2 = smm_mod.scaled_matmul_pallas(x, c, pre=a, interpret=True)
    two = smm_mod.scaled_matmul_pallas(h2, ct, pre=d,
                                       bias=(b @ ct).astype(dtype),
                                       interpret=True)
    err_fac = np.abs(np.asarray(got, np.float64) - want).max()
    err_two = np.abs(np.asarray(two, np.float64) - want).max()
    slack = 1.0 if dtype == jnp.bfloat16 else 1.5
    assert err_fac <= slack * err_two, (err_fac, err_two)


@pytest.mark.parametrize("n,itemsize,bm", [
    (2048, 2, 32), (6144, 2, 32),        # a decode batch: one block
    (2048, 2, 256), (6144, 2, 64),       # a 1024-row prefill
])
def test_pick_bm_is_a_fixed_rule(n, itemsize, bm):
    rows = 32 if bm == 32 else 1024
    assert fac.pick_bm(rows, n, itemsize) == bm
    assert fac.vmem_bytes(n, bm, itemsize) <= fac.VMEM_BUDGET
    assert fac.pick_bm(5, n, itemsize) == 8


@pytest.mark.parametrize("n,family,route", [
    (2048, "acdc", "factored"),
    (6144, "acdc", "factored"),
    (1024, "acdc", "fused"),
    (256, "acdc", "fused"),
    (2048, "circulant", "two_call"),
    (1100, "acdc", "two_call"),          # N not a multiple of 128
])
def test_forward_routing_counter(n, family, route):
    """Each traced forward adds exactly one count, to its route."""
    x = jax.ShapeDtypeStruct((4, n), jnp.bfloat16)
    diag = jax.ShapeDtypeStruct((n,), jnp.float32)
    before = dict(ops.ACDC_FWD_DISPATCHES)
    jax.eval_shape(lambda x, a, d: ops.acdc_fused_op(x, a, d, family=family),
                   x, diag, diag)
    after = dict(ops.ACDC_FWD_DISPATCHES)
    assert {k: after[k] - before[k] for k in after} == {
        k: int(k == route) for k in after}


def test_gradients_keep_the_two_call_backward():
    """The custom VJP's backward is unchanged above MAX_FUSED_N: the
    gradient through the factored forward equals the oracle's."""
    n = fused_mod.MAX_FUSED_N * 2
    x, a, d, b = _layer_inputs((4, n), jnp.float32, 11)

    def loss(f):
        return lambda x, a, d, b: jnp.sum(jnp.tanh(f(x, a, d, b)))

    gk = jax.grad(loss(ops.acdc_fused_op), argnums=(0, 1, 2, 3))(x, a, d, b)
    gr = jax.grad(loss(ref.acdc_fused_ref), argnums=(0, 1, 2, 3))(x, a, d, b)
    for name, k, r_ in zip("xadb", gk, gr):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r_),
                                   atol=2e-4, rtol=1e-3, err_msg=name)
