"""Gradient compression: quantization error bounds + error feedback +
compressed psum under shard_map (multi-device via forked CPU devices is not
available here, so the collective path runs on a 1-device mesh; numerics of
quantize/EF are the meat)."""

import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal install: deterministic replay shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.dist import compression as C


@given(st.integers(1, 2000), st.integers(0, 444))
@settings(max_examples=30, deadline=None)
def test_quantize_error_bound_property(n, seed):
    x = jnp.asarray(np.random.RandomState(seed).randn(n).astype(np.float32))
    q, scale = C.quantize_int8(x)
    xhat = C.dequantize_int8(q, scale, n)
    # per-block max-abs scaling: |err| <= scale/2 elementwise
    blocks = int(np.ceil(n / C.BLOCK))
    per_elem_bound = np.repeat(np.asarray(scale)[:, 0], C.BLOCK)[:n] * 0.5 + 1e-7
    assert bool((np.abs(np.asarray(x - xhat)) <= per_elem_bound).all())


def test_quantize_exact_on_grid():
    """Values already on the int8 grid reconstruct exactly."""
    scale = 0.5
    x = jnp.asarray(np.arange(-127, 128, dtype=np.float32) * scale)
    q, s = C.quantize_int8(x)
    xhat = C.dequantize_int8(q, s, x.shape[0])
    np.testing.assert_allclose(np.asarray(xhat), np.asarray(x), atol=1e-6)


def test_error_feedback_converges():
    """With EF, the *accumulated* transmitted signal tracks the true sum of
    gradients: || sum(g) - sum(ghat) || stays bounded by one quantization
    step instead of growing with T."""
    rng = np.random.RandomState(0)
    n, T = 512, 50
    err = jnp.zeros((n,), jnp.float32)
    true_sum = np.zeros(n, np.float32)
    sent_sum = np.zeros(n, np.float32)
    for t in range(T):
        g = jnp.asarray(rng.randn(n).astype(np.float32))
        flat = g + err
        q, s = C.quantize_int8(flat)
        ghat = C.dequantize_int8(q, s, n)
        err = flat - ghat
        true_sum += np.asarray(g)
        sent_sum += np.asarray(ghat)
    resid = np.abs(true_sum - sent_sum)
    # residual equals |err| <= max scale /2, NOT O(T)
    assert resid.max() < 0.1, resid.max()


def test_compressed_psum_single_device_semantics():
    """On a 1-member axis, compressed_psum returns the dequantized local
    gradient and the quantization residual as new error."""
    from jax.sharding import Mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("pod",))
    g = jnp.asarray(np.random.RandomState(1).randn(64).astype(np.float32))
    e = jnp.zeros_like(g)

    def f(g, e):
        return C.compressed_psum(g, e, "pod")

    ghat, new_e = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P())))(g, e)
    np.testing.assert_allclose(np.asarray(ghat + new_e), np.asarray(g),
                               atol=1e-5)
    # error is bounded by half a quantization step
    q, s = C.quantize_int8(g)
    assert float(jnp.abs(new_e).max()) <= float(s.max()) / 2 + 1e-6


def test_make_error_state_structure():
    params = {"a": jnp.zeros((3, 4), jnp.bfloat16), "b": jnp.zeros((5,))}
    es = C.make_error_state(params)
    assert es["a"].shape == (3, 4) and es["a"].dtype == jnp.float32
    assert es["b"].shape == (5,)
