"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes, plus custom-VJP correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal install: deterministic replay shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import transforms as T
from repro.kernels import ops, ref
from repro.kernels import acdc_fused as fused_mod
from repro.kernels import scaled_matmul as smm_mod


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


SHAPES = [(4, 128), (17, 128), (128, 256), (100, 512), (256, 1024)]


@pytest.mark.parametrize("m,n", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_acdc_fused_vs_oracle(m, n, dtype):
    r = jax.random.PRNGKey(m * 1000 + n)
    x = jax.random.normal(r, (m, n), dtype)
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (n,), dtype)
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (n,), dtype)
    b = 0.1 * jax.random.normal(jax.random.fold_in(r, 3), (n,), dtype)
    got = ops.acdc_fused_op(x, a, d, b)
    want = ref.acdc_fused_ref(x, a, d, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype) * np.sqrt(n), rtol=1e-2)


@pytest.mark.parametrize("n,route", [
    (fused_mod.MAX_FUSED_N * 2, "factored"),
    (fused_mod.MAX_FUSED_N * 2 - 48, "two_call"),   # not a multiple of 128
])
def test_acdc_fused_two_call_path(n, route):
    """N > MAX_FUSED_N: the factored-DCT kernel where N is a multiple of
    128, the chained scaled-matmul implementation elsewhere."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (n,))
    d = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (n,))
    b = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (n,))
    before = ops.ACDC_FWD_DISPATCHES[route]
    got = ops.acdc_fused_op(x, a, d, b)
    assert ops.ACDC_FWD_DISPATCHES[route] == before + 1
    want = ref.acdc_fused_ref(x, a, d, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def test_acdc_fused_no_bias_and_nd_batch():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 128))
    a = jnp.ones((128,))
    d = jnp.ones((128,))
    got = ops.acdc_fused_op(x, a, d, None)
    want = ref.acdc_fused_ref(x, a, d, None)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_acdc_custom_vjp_matches_autodiff_of_oracle():
    m, n = 16, 256
    r = jax.random.PRNGKey(9)
    x = jax.random.normal(r, (m, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (n,))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (n,))
    b = 0.1 * jax.random.normal(jax.random.fold_in(r, 3), (n,))

    def lk(x, a, d, b):
        return jnp.sum(jnp.tanh(ops.acdc_fused_op(x, a, d, b)))

    def lr(x, a, d, b):
        return jnp.sum(jnp.tanh(ref.acdc_fused_ref(x, a, d, b)))

    gk = jax.grad(lk, argnums=(0, 1, 2, 3))(x, a, d, b)
    gr = jax.grad(lr, argnums=(0, 1, 2, 3))(x, a, d, b)
    for name, k, r_ in zip("xadb", gk, gr):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r_),
                                   atol=2e-4, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (64, 256, 512),
                                   (100, 300, 200), (33, 65, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scaled_matmul_vs_oracle(m, k, n, dtype):
    r = jax.random.PRNGKey(m + k + n)
    x = jax.random.normal(r, (m, k), dtype)
    w = jax.random.normal(jax.random.fold_in(r, 1), (k, n), dtype)
    pre = jax.random.normal(jax.random.fold_in(r, 2), (k,), dtype)
    post = jax.random.normal(jax.random.fold_in(r, 3), (n,), dtype)
    bias = jax.random.normal(jax.random.fold_in(r, 4), (n,), dtype)
    got = ops.scaled_matmul(x, w, pre, post, bias)
    want = ref.scaled_matmul_ref(x, w, pre, post, bias)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype) * np.sqrt(k) * 4, rtol=2e-2)


@pytest.mark.parametrize("opts", [
    dict(), dict(pre=True), dict(post=True), dict(bias=True),
    dict(pre=True, post=True, bias=True),
])
def test_scaled_matmul_optional_operands(opts):
    m, k, n = 16, 64, 96
    r = jax.random.PRNGKey(0)
    x = jax.random.normal(r, (m, k))
    w = jax.random.normal(jax.random.fold_in(r, 1), (k, n))
    pre = jax.random.normal(jax.random.fold_in(r, 2), (k,)) if opts.get("pre") else None
    post = jax.random.normal(jax.random.fold_in(r, 3), (n,)) if opts.get("post") else None
    bias = jax.random.normal(jax.random.fold_in(r, 4), (n,)) if opts.get("bias") else None
    got = ops.scaled_matmul(x, w, pre, post, bias)
    want = ref.scaled_matmul_ref(x, w, pre, post, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-4)


@given(st.integers(1, 64), st.sampled_from([128, 256, 384]),
       st.integers(0, 99))
@settings(max_examples=15, deadline=None)
def test_acdc_kernel_property_sweep(m, n, seed):
    r = jax.random.PRNGKey(seed)
    x = jax.random.normal(r, (m, n))
    a = 1 + 0.05 * jax.random.normal(jax.random.fold_in(r, 1), (n,))
    d = 1 + 0.05 * jax.random.normal(jax.random.fold_in(r, 2), (n,))
    got = ops.acdc_fused_op(x, a, d, None)
    want = ref.acdc_fused_ref(x, a, d, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("relu,permute", [(False, False), (True, True)])
def test_acdc_cascade_op_vs_layered_ref(relu, permute):
    """ops.acdc_cascade_op == K chained ref layers with jnp interleaves."""
    n, k, m = 128, 4, 12
    r = jax.random.PRNGKey(21)
    x = jax.random.normal(r, (m, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (k, n))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (k, n))
    b = 0.1 * jax.random.normal(jax.random.fold_in(r, 3), (k, n))
    got = ops.acdc_cascade_op(x, a, d, b, relu=relu, permute=permute)

    perm = jnp.asarray(T.make_riffle(n))
    h = x
    for i in range(k):
        h = ref.acdc_fused_ref(h, a[i], d[i], b[i])
        if i < k - 1:
            if relu:
                h = jnp.maximum(h, 0)
            if permute:
                h = h[..., perm]
    np.testing.assert_allclose(np.asarray(got), np.asarray(h),
                               atol=5e-4, rtol=1e-3)


def test_cascade_vmem_budget_gate():
    """fits_vmem: small cascades fuse; N beyond MAX_FUSED_N never does,
    and the riffle's third transform matrix tightens the budget."""
    from repro.kernels import acdc_cascade_fused as cascade_mod
    assert cascade_mod.fits_vmem(256, 8, permute=True, bias=True)
    assert not cascade_mod.fits_vmem(
        fused_mod.MAX_FUSED_N * 2, 2, permute=False, bias=False)
    assert (cascade_mod.cascade_vmem_bytes(1024, 4, permute=True, bias=True)
            > cascade_mod.cascade_vmem_bytes(1024, 4, permute=False,
                                             bias=True))


def test_kernel_agrees_with_core_acdc():
    """core.acdc(method='pallas') routes through the kernel and matches
    the fft/matmul methods."""
    from repro.core import acdc as A
    n = 256
    r = jax.random.PRNGKey(4)
    x = jax.random.normal(r, (6, n))
    a = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 1), (n,))
    d = 1 + 0.1 * jax.random.normal(jax.random.fold_in(r, 2), (n,))
    yp = A.acdc(x, a, d, method="pallas")
    yf = A.acdc(x, a, d, method="fft")
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yf),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Block-size autotuning (first-call sweep, memoized; fixed fallback on CPU).
# ---------------------------------------------------------------------------

def test_autotune_cpu_fallback_keeps_fixed_constants():
    """Off-device the sweep is skipped: the pre-autotune constants come
    back (256 fwd / 128 bwd / budget-derived cascade) and are memoized."""
    from repro.kernels import acdc_bwd as bwd_mod
    from repro.kernels import acdc_cascade_fused as cascade_mod
    from repro.kernels import autotune
    assert jax.default_backend() != "tpu"  # this suite runs on CPU
    assert autotune.autotuned_bm("fwd", 512) == fused_mod.DEFAULT_BM
    assert autotune.autotuned_bm("bwd", 512) == bwd_mod.DEFAULT_BM
    assert autotune.autotuned_bm(
        "cascade", 1024, 4, bias=True, permute=True) == cascade_mod.pick_bm(
            1024, 4, permute=True, bias=True)
    key = ("fwd", 512, 1, "float32", False, False, "acdc")
    assert autotune._CACHE[key] == fused_mod.DEFAULT_BM


def test_autotune_sweep_picks_fastest_candidate():
    """The sweep returns the argmin of the injected timer and only ever
    considers candidates inside the cascade VMEM budget."""
    from repro.kernels import autotune

    fake = {64: 3.0, 128: 1.0, 256: 2.0}
    bm = autotune.sweep("fwd", 128, interpret=True,
                        timer=lambda thunk: fake[thunk.bm])
    assert bm == 128
    # riffled N=1024 cascades exceed the budget at bm=128/256: only 64
    # may be timed, whatever the timer says
    cands = autotune._candidates("cascade", 1024, 4, bias=True, permute=True)
    assert cands == [64]


def test_autotune_sweep_runs_kernels_in_interpret_mode():
    """End-to-end: the default timer path dispatches every direction's
    kernel (interpret mode) and returns a legal candidate."""
    from repro.kernels import autotune
    for direction in ("fwd", "bwd", "cascade", "cascade_bwd"):
        bm = autotune.sweep(direction, 128, 2, bias=True, interpret=True,
                            timer=None)
        assert bm in autotune.CANDIDATE_BMS


def test_autotune_cascade_bwd_fallback_is_budget_derived():
    """Off-device the cascade_bwd direction answers with the reverse-sweep
    module's own pick_bm (stash-inclusive budget), not the forward's."""
    from repro.kernels import acdc_cascade_bwd as cbwd_mod
    from repro.kernels import autotune
    got = autotune.autotuned_bm("cascade_bwd", 256, 4, bias=True,
                                permute=True)
    assert got == cbwd_mod.pick_bm(256, 4, permute=True, bias=True)


def test_autotune_persistent_cache_roundtrip(tmp_path, monkeypatch):
    """Swept winners spill to JSON and reload in a fresh process-alike
    (cleared memo); entries from a different backend are ignored; the
    env kill-switch disables both directions."""
    from repro.kernels import autotune

    path = tmp_path / "autotune_cache.json"
    monkeypatch.setenv(autotune.CACHE_ENV + "_PATH", str(path))
    monkeypatch.setattr(autotune, "_backend", lambda: "tpu")
    monkeypatch.setattr(autotune, "sweep",
                        lambda *a, **kw: 64)  # pretend the device sweep ran
    monkeypatch.setattr(autotune, "_CACHE", {})
    monkeypatch.setattr(autotune, "_PERSIST_LOADED", False)

    assert autotune.autotuned_bm("cascade_bwd", 256, 4, bias=True) == 64
    assert path.exists()

    # fresh process: memo cleared, sweep would now answer differently —
    # the persisted winner must be preferred (no re-sweep).
    monkeypatch.setattr(autotune, "sweep", lambda *a, **kw: 128)
    monkeypatch.setattr(autotune, "_CACHE", {})
    monkeypatch.setattr(autotune, "_PERSIST_LOADED", False)
    assert autotune.autotuned_bm("cascade_bwd", 256, 4, bias=True) == 64

    # a different backend must NOT consume the file: non-TPU answers are
    # the budget-derived fallback, never the persisted TPU winner.
    from repro.kernels import acdc_cascade_bwd as cbwd_mod
    monkeypatch.setattr(autotune, "_backend", lambda: "gpu")
    monkeypatch.setattr(autotune, "_CACHE", {})
    monkeypatch.setattr(autotune, "_PERSIST_LOADED", False)
    fallback = cbwd_mod.pick_bm(256, 4, permute=False, bias=True)
    assert fallback != 64
    assert autotune.autotuned_bm("cascade_bwd", 256, 4, bias=True) == fallback

    # kill switch: no load, no save.
    monkeypatch.setenv(autotune.CACHE_ENV, "0")
    monkeypatch.setattr(autotune, "_backend", lambda: "tpu")
    monkeypatch.setattr(autotune, "sweep", lambda *a, **kw: 256)
    monkeypatch.setattr(autotune, "_CACHE", {})
    monkeypatch.setattr(autotune, "_PERSIST_LOADED", False)
    path.unlink()
    assert autotune.autotuned_bm("cascade_bwd", 256, 4, bias=True) == 256
    assert not path.exists()


def test_autotune_cpu_never_touches_persistent_cache(tmp_path, monkeypatch):
    """CPU fallback answers must neither read nor write the device cache
    (a persisted CPU constant would silently skip a real TPU sweep)."""
    from repro.kernels import autotune

    path = tmp_path / "autotune_cache.json"
    path.write_text('{"backend": "tpu", "entries": {"fwd|512|1|float32|'
                    'False|False": 32}}')
    monkeypatch.setenv(autotune.CACHE_ENV + "_PATH", str(path))
    monkeypatch.setattr(autotune, "_CACHE", {})
    monkeypatch.setattr(autotune, "_PERSIST_LOADED", False)
    assert jax.default_backend() != "tpu"
    assert autotune.autotuned_bm("fwd", 512) == fused_mod.DEFAULT_BM  # not 32


def test_autotune_sweep_executes_inside_jit_trace():
    """The sweep's only production call sites are first hit INSIDE a jit
    trace; the compile-time-eval operand build plus AOT-compiled kernel
    dispatch must execute concretely (timing real work) instead of being
    staged into the caller's jaxpr.  Covers every direction including the
    backward kernel's program_id/scratch machinery."""
    from repro.kernels import autotune

    seen = {}

    @jax.jit
    def traced(y):
        for direction in ("fwd", "bwd", "cascade"):
            seen[direction] = autotune.sweep(direction, 128, 2, bias=True,
                                             interpret=True, timer=None)
        return y

    traced(jnp.ones(()))
    for direction in ("fwd", "bwd", "cascade"):
        assert isinstance(seen[direction], int)
        assert seen[direction] in autotune.CANDIDATE_BMS
