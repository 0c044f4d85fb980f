"""Ahead-of-time compiles of the main path's Pallas kernels for a v5e.

The TPU compiler is installed without a chip attached: each test lowers a
kernel with ``interpret=False`` for a described ``v5e:2x2`` topology and
compiles it for one of its chips, at the shapes ``qwen3_1_7b`` serves and
trains with.  That catches what interpret mode cannot: block shapes the
Mosaic verifier refuses and kernels over the VMEM limit.

The topology is described inside a fixture (never at import): only one
process may load libtpu at a time, and the test workers import every test
file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import acdc_bwd
from repro.kernels import acdc_factored
from repro.kernels import acdc_cascade_bwd as cascade_bwd
from repro.kernels import acdc_cascade_fused as cascade_fused
from repro.kernels import paged_attn
from repro.kernels import scaled_matmul

# Qwen3-1.7B serving shapes: 8 KV heads of 128 in groups of 2, 16-token
# pages, 4 slots of 1024 + 32 + 1 positions
HKV, GROUP, DH, BS, SLOTS = 8, 2, 128, 16, 4
MAX_BLOCKS = -(-(1024 + 32 + 1) // BS)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no describer
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("t", [1, 5], ids=["decode", "verify"])
def test_paged_attention_compiles(spec, t):
    blk = paged_attn.pick_block(hkv=HKV, dh=DH, group=GROUP, t=t, bs=BS,
                                itemsize=2)
    assert blk is not None
    pool = SLOTS * MAX_BLOCKS + 1          # + the trash page
    bf16 = jnp.bfloat16
    fn = functools.partial(paged_attn.paged_attention, softcap=0.0,
                           page_chunk=blk[0], head_block=blk[1])
    compiled = _compile(
        fn, spec((SLOTS, t, HKV * GROUP, DH), bf16),
        spec((SLOTS, t, HKV, DH), bf16), spec((SLOTS, t, HKV, DH), bf16),
        spec((pool, BS, HKV, DH), bf16), spec((pool, BS, HKV, DH), bf16),
        spec((SLOTS, MAX_BLOCKS), jnp.int32), spec((SLOTS,), jnp.int32),
        spec((), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [4, 1024], ids=["decode", "prefill"])
@pytest.mark.parametrize("n", [2048, 6144])
def test_scaled_matmul_compiles(spec, n, rows):
    """The two-call kernels above ``MAX_FUSED_N`` at qwen3's attn_out
    (2048) and padded-square mlp (6144) widths: the backward's building
    block, and the forward of the families without a factored kernel."""
    compiled = _compile(
        lambda x, w, pre: scaled_matmul.scaled_matmul_pallas(x, w, pre=pre),
        spec((rows, n), jnp.bfloat16), spec((n, n), jnp.float32),
        spec((n,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [32, 1024], ids=["decode", "prefill"])
@pytest.mark.parametrize("n", [2048, 6144])
def test_factored_acdc_forward_compiles(spec, n, rows):
    """The factored-DCT forward those cascades take instead, at its fixed
    row block: bf16 activations, fp32 diagonals."""
    bm = acdc_factored.pick_bm(rows, n, 2)
    f32 = jnp.float32
    compiled = _compile(
        lambda x, a, d: acdc_factored.acdc_factored_pallas(x, a, d, None,
                                                           bm=bm),
        spec((rows, n), jnp.bfloat16), spec((n,), f32), spec((n,), f32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [2048, 6144])
def test_per_layer_acdc_backward_compiles(spec, n):
    """The backward those cascades take in training."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    compiled = _compile(
        lambda x, g, a, d, c, ct: acdc_bwd.acdc_bwd_two_call(
            x, g, a, d, c, ct, with_bias=False),
        spec((512, n), bf16), spec((512, n), bf16), spec((n,), f32),
        spec((n,), f32), spec((n, n), f32), spec((n, n), f32))
    assert "tpu_custom_call" in compiled.as_text()


N_FUSED, K_FUSED = 1024, 3


def _cascade_operands(spec):
    n, k, f32 = N_FUSED, K_FUSED, jnp.float32
    diag = [spec((k, n), f32)] * 3                 # a, d, bias
    mats = [spec((n, n), f32)] * 3                 # C, C^T, riffled C^T
    return diag, mats


def test_fused_cascade_forward_compiles(spec):
    bm = cascade_fused.pick_bm(N_FUSED, K_FUSED, permute=True, bias=True)
    assert bm is not None
    diag, mats = _cascade_operands(spec)
    fn = functools.partial(cascade_fused.acdc_cascade_pallas, relu=True,
                           bm=bm)
    compiled = _compile(fn, spec((512, N_FUSED), jnp.bfloat16), *diag, *mats)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_cascade_backward_compiles(spec):
    bm = cascade_bwd.pick_bm(N_FUSED, K_FUSED, permute=True, bias=True)
    assert bm is not None
    diag, mats = _cascade_operands(spec)
    fn = functools.partial(cascade_bwd.acdc_cascade_bwd_pallas, relu=True,
                           bm=bm)
    x = spec((512, N_FUSED), jnp.bfloat16)
    compiled = _compile(fn, x, x, *diag, *mats)
    assert "tpu_custom_call" in compiled.as_text()
