"""Gradient parity of the fused Pallas backward + cascade fusion vs jnp
oracles (interpret mode on CPU, compiled on TPU).

Coverage matrix from the fused-training-hot-path issue:

* fused backward vs ``jax.grad`` of the jnp reference across BOTH N
  regimes (<= and > ``MAX_FUSED_N``), with/without bias, fp32 and bf16;
* direct VJP outputs vs the four-matmul reference formulation;
* cascade-fused forward vs the ``acdc_cascade`` oracle with ReLU/riffle
  on and off, plus cascade-level gradient parity;
* reverse-sweep cascade backward vs the per-layer-scan oracle across
  {relu} x {riffle} x {fp32, bf16-with-fp32-masters} x ragged rows,
  with routing assertions (in-budget -> reverse sweep, over-budget ->
  scan fallback, gradients unchanged either way);
* the model zoo's ``linear_apply`` projections and the ``dist/steps.py``
  train step pick the pallas path up unchanged (including the
  reverse-sweep backward in the train step's VJP).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import acdc as A
from repro.kernels import acdc_fused as fused_mod
from repro.kernels import ops, ref

SMALL_N = 256                       # single fused kernel regime
BIG_N = fused_mod.MAX_FUSED_N * 2   # two-call scaled_matmul regime


def _layer(n, dtype=jnp.float32, seed=0):
    r = jax.random.PRNGKey(seed)
    m = 4 if n > fused_mod.MAX_FUSED_N else 16
    x = jax.random.normal(r, (m, n), dtype)
    # diagonals stay fp32 masters — the kernels take them uncast
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (n,))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (n,))
    b = 0.1 * jax.random.normal(jax.random.fold_in(r, 3), (n,))
    return x, a, d, b


def _grad_tol(dtype, n):
    return 1e-4 * np.sqrt(n / 128) if dtype == jnp.float32 else 5e-2


@pytest.mark.parametrize("n", [SMALL_N, BIG_N])
@pytest.mark.parametrize("bias", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_backward_matches_autodiff_of_oracle(n, bias, dtype):
    x, a, d, b = _layer(n, dtype)
    args = (x, a, d, b) if bias else (x, a, d)
    argnums = tuple(range(len(args)))

    def lk(*args):
        return jnp.sum(jnp.tanh(ops.acdc_fused_op(*args).astype(jnp.float32)))

    def lr(*args):
        return jnp.sum(jnp.tanh(ref.acdc_fused_ref(*args).astype(jnp.float32)))

    gk = jax.grad(lk, argnums=argnums)(*args)
    gr = jax.grad(lr, argnums=argnums)(*args)
    for name, got, want in zip("xadb", gk, gr):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=_grad_tol(dtype, n), rtol=2e-2 if dtype == jnp.bfloat16
            else 1e-3, err_msg=f"{name} n={n}")


@pytest.mark.parametrize("n", [128, SMALL_N])
def test_vjp_outputs_match_four_matmul_reference(n):
    """The fused kernel's raw VJP cotangents equal the eq. 10-14 reference
    (the four-matmul formulation it replaced), not just chained grads."""
    x, a, d, b = _layer(n, seed=n)
    g = jax.random.normal(jax.random.PRNGKey(99), x.shape)
    _, vjp = jax.vjp(ops.acdc_fused, x, a, d, b)
    dx, da, dd, db = vjp(g)
    rx, ra, rd, rb = ref.acdc_bwd_ref(x, a, d, g)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), atol=1e-4)
    np.testing.assert_allclose(np.asarray(da), np.asarray(ra), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(rb), atol=1e-4,
                               rtol=1e-4)


def test_mixed_dtype_bias_cotangent():
    """bf16 diagonals with an fp32 bias (reachable now that the pallas
    path takes master params uncast): each cotangent must match its own
    primal's dtype, not d's."""
    n = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (8, n), jnp.bfloat16)
    a = jnp.ones((n,), jnp.bfloat16)
    d = jnp.ones((n,), jnp.bfloat16)
    b = jnp.zeros((n,), jnp.float32)
    g = jax.grad(lambda x, a, d, b: jnp.sum(
        ops.acdc_fused_op(x, a, d, b).astype(jnp.float32)),
        argnums=(0, 1, 2, 3))(x, a, d, b)
    assert g[0].dtype == jnp.bfloat16
    assert g[1].dtype == jnp.bfloat16
    assert g[3].dtype == jnp.float32


def test_fused_backward_ragged_rows_ignore_padding():
    """Row counts that don't divide the block size: zero-padded rows must
    contribute nothing to the diagonal reductions."""
    n = 128
    r = jax.random.PRNGKey(3)
    x = jax.random.normal(r, (13, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (n,))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (n,))
    g = jax.random.normal(jax.random.fold_in(r, 4), (13, n))
    _, vjp = jax.vjp(ops.acdc_fused_nobias, x, a, d)
    dx, da, dd = vjp(g)
    rx, ra, rd, _ = ref.acdc_bwd_ref(x, a, d, g)
    np.testing.assert_allclose(np.asarray(da), np.asarray(ra), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), atol=1e-4)


def test_nd_batch_gradients():
    """ND inputs flatten through the VJP and come back in shape."""
    n = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, n))
    a = jnp.ones((n,))
    d = 1.5 * jnp.ones((n,))

    gk = jax.grad(lambda x: jnp.sum(ops.acdc_fused_op(x, a, d) ** 2))(x)
    gr = jax.grad(lambda x: jnp.sum(ref.acdc_fused_ref(x, a, d) ** 2))(x)
    assert gk.shape == x.shape
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-4)


# ---------------------------------------------------------------------------
# Whole-cascade fusion.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("permute", [False, True])
@pytest.mark.parametrize("bias", [False, True])
def test_cascade_fused_forward_vs_oracle(relu, permute, bias):
    n, k = 128, 3
    kw = dict(n=n, k=k, relu=relu, permute=permute, bias=bias)
    cfg_p = A.ACDCConfig(method="pallas", **kw)
    cfg_o = A.ACDCConfig(method="matmul", **kw)
    p = A.init_acdc_params(jax.random.PRNGKey(11), cfg_p)
    if bias:
        p["bias"] = p["bias"] + 0.05  # nonzero so the bias path is live
    x = jax.random.normal(jax.random.PRNGKey(1), (10, n))
    got = A.acdc_cascade(p, x, cfg_p)
    want = A.acdc_cascade(p, x, cfg_o)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("relu,permute,bias", [
    (False, False, False), (True, True, True), (True, False, False),
])
def test_cascade_fused_gradients_vs_oracle(relu, permute, bias):
    n, k = 128, 3
    kw = dict(n=n, k=k, relu=relu, permute=permute, bias=bias)
    cfg_p = A.ACDCConfig(method="pallas", **kw)
    cfg_o = A.ACDCConfig(method="matmul", **kw)
    p = A.init_acdc_params(jax.random.PRNGKey(13), cfg_p)
    if bias:
        p["bias"] = p["bias"] + 0.05
    x = jax.random.normal(jax.random.PRNGKey(2), (8, n))

    def loss(cfg):
        return lambda p, x: jnp.sum(jnp.tanh(A.acdc_cascade(p, x, cfg)))

    gp, gxp = jax.grad(loss(cfg_p), argnums=(0, 1))(p, x)
    go, gxo = jax.grad(loss(cfg_o), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(np.asarray(gxp), np.asarray(gxo), atol=2e-4,
                               rtol=1e-3)
    for key in gp:
        np.testing.assert_allclose(
            np.asarray(gp[key]), np.asarray(go[key]), atol=2e-4, rtol=1e-3,
            err_msg=key)


def test_cascade_fused_bf16_activation_fp32_masters():
    """bf16 residual stream with fp32 master diagonals: output dtype
    follows the activation, gradients follow the parameters."""
    n, k = 128, 2
    cfg = A.ACDCConfig(n=n, k=k, relu=True, bias=False, method="pallas")
    p = A.init_acdc_params(jax.random.PRNGKey(5), cfg)  # fp32 masters
    x = jax.random.normal(jax.random.PRNGKey(6), (8, n), jnp.bfloat16)
    y = A.acdc_cascade(p, x, cfg)
    assert y.dtype == jnp.bfloat16
    g = jax.grad(lambda p: jnp.sum(
        A.acdc_cascade(p, x, cfg).astype(jnp.float32)))(p)
    assert g["a"].dtype == jnp.float32
    cfg_o = A.ACDCConfig(n=n, k=k, relu=True, bias=False, method="matmul")
    g_o = jax.grad(lambda p: jnp.sum(
        A.acdc_cascade(p, x, cfg_o).astype(jnp.float32)))(p)
    np.testing.assert_allclose(np.asarray(g["a"]), np.asarray(g_o["a"]),
                               atol=0.3, rtol=0.1)


def test_cascade_fallback_beyond_vmem_budget():
    """N above MAX_FUSED_N: the cascade op must fall back to the
    per-layer path and still match the oracle (fwd + grads)."""
    n, k = fused_mod.MAX_FUSED_N * 2, 2
    cfg_p = A.ACDCConfig(n=n, k=k, relu=True, bias=False, method="pallas")
    cfg_o = A.ACDCConfig(n=n, k=k, relu=True, bias=False, method="fft")
    p = A.init_acdc_params(jax.random.PRNGKey(7), cfg_p)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, n))
    np.testing.assert_allclose(
        np.asarray(A.acdc_cascade(p, x, cfg_p)),
        np.asarray(A.acdc_cascade(p, x, cfg_o)), atol=2e-3, rtol=1e-3)
    gp = jax.grad(lambda p: jnp.sum(jnp.tanh(A.acdc_cascade(p, x, cfg_p))))(p)
    go = jax.grad(lambda p: jnp.sum(jnp.tanh(A.acdc_cascade(p, x, cfg_o))))(p)
    np.testing.assert_allclose(np.asarray(gp["d"]), np.asarray(go["d"]),
                               atol=2e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# Reverse-sweep cascade backward (kernels/acdc_cascade_bwd.py).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("permute", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows", [16, 13])  # block-aligned and ragged
def test_reverse_sweep_backward_matches_scan_oracle(relu, permute, dtype,
                                                    rows):
    """The reverse-sweep kernel's raw cotangents equal the per-layer-scan
    path it replaced (ops._cascade_bwd_core), for every interleave combo,
    fp32 and bf16-with-fp32-masters, aligned and ragged row counts."""
    n, k = 128, 3
    r = jax.random.PRNGKey(17)
    x = jax.random.normal(r, (rows, n), dtype)
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (k, n))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (k, n))
    b = 0.05 + 0.1 * jax.random.normal(jax.random.fold_in(r, 3), (k, n))
    g = jax.random.normal(jax.random.fold_in(r, 4), (rows, n), dtype)

    got = ops._cascade_bwd_fused(relu, permute, x, a, d, b, g)
    # bf16: the reverse sweep (like the fused forward) keeps the
    # rematerialized activations fp32 on-chip, so its oracle is the scan
    # in fp32 on the same bf16 inputs.  A bf16 scan is no oracle: it
    # rounds every layer's activations back to bf16, and where a ReLU
    # input sits near zero that flips the mask and moves dd by ~7% of
    # its scale, while the reverse sweep stays within bf16 output
    # rounding of the fp32 scan.
    f32 = jnp.float32
    want = ops._cascade_bwd_core(relu, permute, x.astype(f32), a, d, b,
                                 g.astype(f32))
    atol = 2e-4 if dtype == jnp.float32 else 3e-2
    rtol = 1e-3 if dtype == jnp.float32 else 1e-2
    for name, gv, wv in zip(("dx", "da", "dd", "db"), got, want):
        assert gv.dtype == (dtype if name == "dx" else jnp.float32), name
        np.testing.assert_allclose(
            np.asarray(gv, np.float32), np.asarray(wv, np.float32),
            atol=atol, rtol=rtol, err_msg=f"{name} relu={relu} "
            f"permute={permute} rows={rows}")


def test_reverse_sweep_backward_nobias_matches_scan_oracle():
    n, k = 128, 4
    r = jax.random.PRNGKey(23)
    x = jax.random.normal(r, (10, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (k, n))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (k, n))
    g = jax.random.normal(jax.random.fold_in(r, 3), (10, n))
    got = ops._cascade_bwd_fused(True, True, x, a, d, None, g)
    want = ops._cascade_bwd_core(True, True, x, a, d, None, g)
    assert len(got) == 3  # no dbias entry for the bias-free primitive
    for name, gv, wv in zip(("dx", "da", "dd"), got, want):
        np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                   atol=2e-4, rtol=1e-3, err_msg=name)


def test_cascade_backward_routes_reverse_sweep_in_budget():
    """Fused-regime cascades must take the reverse-sweep VJP (the CI
    dispatch-regression gate counts exactly this)."""
    n, k = 128, 3
    cfg = A.ACDCConfig(n=n, k=k, relu=True, permute=True, bias=True,
                       method="pallas")
    p = A.init_acdc_params(jax.random.PRNGKey(29), cfg)
    x = jax.random.normal(jax.random.PRNGKey(30), (8, n))
    before = dict(ops.CASCADE_BWD_DISPATCHES)
    jax.grad(lambda p: jnp.sum(jnp.tanh(A.acdc_cascade(p, x, cfg))))(p)
    assert ops.CASCADE_BWD_DISPATCHES["reverse_sweep"] == \
        before["reverse_sweep"] + 1
    assert ops.CASCADE_BWD_DISPATCHES["per_layer_scan"] == \
        before["per_layer_scan"]


def test_cascade_backward_over_budget_falls_back_to_scan(monkeypatch):
    """When the stash-inclusive backward budget doesn't fit, the forward
    can stay fused while the backward routes to the per-layer scan — and
    gradients must be unchanged."""
    from repro.kernels import acdc_cascade_bwd as cbwd_mod

    n, k = 128, 3
    cfg = A.ACDCConfig(n=n, k=k, relu=True, permute=True, bias=False,
                       method="pallas")
    p = A.init_acdc_params(jax.random.PRNGKey(31), cfg)
    x = jax.random.normal(jax.random.PRNGKey(32), (8, n))

    def loss(p):
        return jnp.sum(jnp.tanh(A.acdc_cascade(p, x, cfg)))

    want = jax.grad(loss)(p)
    monkeypatch.setattr(cbwd_mod, "pick_bm",
                        lambda *a, **kw: None)  # force over-budget
    before = dict(ops.CASCADE_BWD_DISPATCHES)
    got = jax.grad(loss)(p)
    assert ops.CASCADE_BWD_DISPATCHES["per_layer_scan"] == \
        before["per_layer_scan"] + 1
    for key in want:
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]),
                                   atol=2e-4, rtol=1e-3, err_msg=key)


def test_reverse_sweep_rejects_k1():
    from repro.kernels import acdc_cascade_bwd as cbwd_mod
    from repro.core import transforms

    n = 128
    c = transforms.dct_matrix(n)
    ct = transforms.idct_matrix(n)
    with pytest.raises(ValueError, match="K >= 2"):
        cbwd_mod.acdc_cascade_bwd_pallas(
            jnp.ones((8, n)), jnp.ones((8, n)), jnp.ones((1, n)),
            jnp.ones((1, n)), None, c, ct, None, interpret=True)


def test_reverse_sweep_budget_shrinks_block_with_depth():
    """pick_bm must account for the (K-1)-deep VMEM stash: deep riffled
    cascades at MAX_FUSED_N get a smaller block or fall back entirely."""
    from repro.kernels import acdc_cascade_bwd as cbwd_mod

    shallow = cbwd_mod.pick_bm(256, 2, permute=True, bias=True)
    deep = cbwd_mod.pick_bm(fused_mod.MAX_FUSED_N, 4, permute=True,
                            bias=True)
    assert shallow is not None
    assert deep is None or deep < shallow
    assert cbwd_mod.pick_bm(fused_mod.MAX_FUSED_N * 2, 2, permute=False,
                            bias=False) is None


def test_cascade_k1_degenerates_to_single_layer():
    n = 128
    cfg = A.ACDCConfig(n=n, k=1, bias=True, method="pallas")
    p = A.init_acdc_params(jax.random.PRNGKey(9), cfg)
    x = jax.random.normal(jax.random.PRNGKey(10), (6, n))
    got = A.acdc_cascade(p, x, cfg)
    want = ref.acdc_fused_ref(x, p["a"][0], p["d"][0], p["bias"][0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# ---------------------------------------------------------------------------
# Integration: model zoo projections + dist train step.
# ---------------------------------------------------------------------------

def test_linear_apply_pallas_matches_matmul_method():
    """The zoo's projection factory picks up the fused cascade unchanged:
    same params, same output, only the method differs."""
    from repro.configs import registry
    from repro.models import linear as linear_mod

    cfg = registry.get_smoke_config("qwen3_1_7b")
    cfg_p = dataclasses.replace(cfg, sell_kind="acdc", sell_method="pallas")
    cfg_m = dataclasses.replace(cfg, sell_kind="acdc", sell_method="matmul")
    n_in = n_out = 256
    params = linear_mod.linear_init(jax.random.PRNGKey(0), n_in, n_out,
                                    cfg_p, role="mlp_in")
    assert "sell" in params
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, n_in))
    yp = linear_mod.linear_apply(params, x, n_in, n_out, cfg_p, "mlp_in")
    ym = linear_mod.linear_apply(params, x, n_in, n_out, cfg_m, "mlp_in")
    assert yp.shape == (2, 4, n_out)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(ym), atol=2e-4,
                               rtol=1e-3)


@pytest.mark.slow
def test_train_step_runs_with_pallas_sell():
    """dist/steps.make_train_step trains through the fused cascade VJP —
    and its backward picks up the reverse-sweep kernel (the smoke SELL
    cascades are K>=2 and well inside the VMEM budget, so a per-layer
    routing here would be a dispatch regression)."""
    from repro.configs import registry
    from repro.data import DataConfig, SyntheticLM
    from repro.dist import steps as steps_mod
    from repro.models import get_model
    from repro.optim import OptimizerConfig, constant_schedule, make_optimizer

    cfg = registry.get_smoke_config("qwen3_1_7b")
    cfg = dataclasses.replace(cfg, sell_kind="acdc", sell_method="pallas")
    model = get_model(cfg)
    opt = make_optimizer(OptimizerConfig(lr=1e-3, weight_decay=0.0),
                         constant_schedule(1e-3))
    step = jax.jit(steps_mod.make_train_step(model, cfg, opt))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=2))
    state = steps_mod.init_state(model, cfg, opt, jax.random.PRNGKey(0))
    before = dict(ops.CASCADE_BWD_DISPATCHES)
    state, m0 = step(state, data.batch_at(0))
    state, m1 = step(state, data.batch_at(1))
    assert np.isfinite(float(m0["loss"])) and np.isfinite(float(m1["loss"]))
    assert int(state["step"]) == 2
    assert ops.CASCADE_BWD_DISPATCHES["reverse_sweep"] > \
        before["reverse_sweep"]
    assert ops.CASCADE_BWD_DISPATCHES["per_layer_scan"] == \
        before["per_layer_scan"]


# ---------------------------------------------------------------------------
# Transform-family parity (core/families.py): the fused kernel stack is
# family-generic — every registered real-orthonormal family must produce
# the same forward and cotangents through the fused whole-cascade path as
# through the per-layer jnp scan.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["acdc", "circulant", "hadamard"])
@pytest.mark.parametrize("bias", [True, False])
def test_cascade_grads_fused_matches_scan_per_family(family, bias):
    n, k, m = 128, 3, 9
    r = jax.random.PRNGKey(53)
    x = jax.random.normal(r, (m, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (k, n))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (k, n))
    b = 0.05 * jax.random.normal(jax.random.fold_in(r, 3), (k, n)) \
        if bias else None
    g = jax.random.normal(jax.random.fold_in(r, 4), (m, n))

    def fused(x, a, d, b):
        return ops.acdc_cascade_op(x, a, d, b, relu=True, permute=True,
                                   family=family)

    def scan(x, a, d, b):
        return ops._cascade_per_layer(x, a, d, b, True, True,
                                      family=family)

    if bias:
        y_f, vjp_f = jax.vjp(fused, x, a, d, b)
        y_s, vjp_s = jax.vjp(scan, x, a, d, b)
    else:
        y_f, vjp_f = jax.vjp(lambda x, a, d: fused(x, a, d, None), x, a, d)
        y_s, vjp_s = jax.vjp(lambda x, a, d: scan(x, a, d, None), x, a, d)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_s),
                               atol=2e-4, rtol=1e-3, err_msg=family)
    for name, gf, gs in zip(("dx", "da", "dd", "db"),
                            vjp_f(g), vjp_s(g)):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gs), atol=2e-4, rtol=1e-3,
            err_msg=f"{family} {name} bias={bias}")


@pytest.mark.parametrize("family", ["circulant", "hadamard"])
def test_reverse_sweep_backward_per_family(family):
    """The reverse-sweep kernel's raw cotangents match the per-layer-scan
    core for the non-DCT families too (same kernel body, different C)."""
    n, k, m = 128, 3, 10
    r = jax.random.PRNGKey(59)
    x = jax.random.normal(r, (m, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (k, n))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (k, n))
    b = 0.05 * jax.random.normal(jax.random.fold_in(r, 3), (k, n))
    g = jax.random.normal(jax.random.fold_in(r, 4), (m, n))
    got = ops._cascade_bwd_fused(True, True, x, a, d, b, g, family=family)
    want = ops._cascade_bwd_core(True, True, x, a, d, b, g, family=family)
    for name, gv, wv in zip(("dx", "da", "dd", "db"), got, want):
        np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                   atol=2e-4, rtol=1e-3,
                                   err_msg=f"{family} {name}")
