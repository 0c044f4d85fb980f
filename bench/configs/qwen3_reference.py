"""Plain float32 reference of the Qwen3 decoder with dense or ACDC projections.

Straight ``jax.numpy`` at ``precision="highest"``: no kernels, no KV cache,
no batching tricks, and nothing imported from the program under test.  It
reads the weights the benchmark drew from the seed (``bench/harness/
weights.py``), in the parameter layout the program serves them in:

* ``embed/table`` (V, D), tied to the output head;
* per layer (stacked on a leading L axis): ``norm1/scale``, ``norm2/scale``
  (RMSNorm, stored as scale - 1), ``attn/{wq,wk,wv}/w`` dense,
  ``attn/{q_norm,k_norm}/scale`` (per-head RMSNorm, Qwen3's qk-norm),
  ``attn/wo`` and ``mlp/{wg,wu,wd}`` either dense (``w``, in x out) or an
  ACDC cascade (``sell/a``, ``sell/d``: (K, N) diagonals);
* ``final_norm/scale``.

The layer equations (Qwen3, Hugging Face ``Qwen3ForCausalLM``):
``x += Wo . attn(rope(qnorm(Wq h)), rope(knorm(Wk h)), Wv h)`` with
``h = rmsnorm(x)``, causal grouped-query attention (query head ``i`` reads
key/value head ``i // (Hq / Hkv)``), scale ``Dh**-0.5``; then
``x += Wd (silu(Wg h) * Wu h)``; logits ``= rmsnorm(x) . E^T``.

Two departures from the Hugging Face module, both the program's
conventions: RoPE rotates interleaved pairs ``(x[2i], x[2i+1])`` instead
of the two halves (the same model up to a fixed permutation of the q/k
head dims), and with ``sell = "acdc"`` the attention output and the three
MLP projections are the paper's cascade: ``y = x . A1 C D1 C^T . P .
A2 C D2 C^T`` on the input zero-padded to ``N = max(in, out)`` rounded up
to 128, truncated to ``out``; ``C`` the orthonormal DCT-II, ``P`` the
riffle (perfect shuffle) between the K = 2 layers.

``quant="fp8"`` is the control: every projection, transform and the head
take both operands rounded to float8 e4m3 (per-tensor absmax scale), the
accumulation in float32.  It is the next precision below the bfloat16 the
configurations serve in, and it must fail the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def dct_matrix(n: int) -> np.ndarray:
    """Orthonormal DCT-II, ``y = x @ C``; ``C^-1 = C^T``."""
    m = np.arange(n)[:, None]
    k = np.arange(n)[None, :]
    c = np.cos(np.pi * (2.0 * m + 1.0) * k / (2.0 * n)) * np.sqrt(2.0 / n)
    c[:, 0] /= np.sqrt(2.0)
    return c.astype(np.float32)


def riffle(n: int) -> np.ndarray:
    """Perfect shuffle: ``[0, n/2, 1, n/2 + 1, ...]``."""
    half = (n + 1) // 2
    idx = np.empty((n,), np.int32)
    idx[0::2] = np.arange(half)
    idx[1::2] = np.arange(half, n)
    return idx


def op_size(n_in: int, n_out: int) -> int:
    return int(-(-max(n_in, n_out) // 128) * 128)


def _round(x, quant):
    if quant is None:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * s


def _mm(x, w, quant):
    return jnp.matmul(_round(x, quant), _round(w, quant), precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, positions, theta):
    """x: (S, H, Dh); interleaved pairs."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def project(p, x, n_out, mats, quant):
    """One projection: dense ``x @ w`` or the ACDC cascade."""
    if "w" in p:
        return _mm(x, p["w"], quant)
    a, d = p["sell"]["a"], p["sell"]["d"]
    n = a.shape[-1]
    c = mats[n]
    h = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])])
    perm = riffle(n)
    for i in range(a.shape[0]):
        h = _mm(h * a[i], c, quant) * d[i]
        h = _mm(h, c.T, quant)
        if i < a.shape[0] - 1:
            h = h[..., perm]
    return h[..., :n_out]


def layer(lp, x, positions, cfg, mats, quant):
    """One decoder block on one sequence ``x`` (S, D)."""
    s = x.shape[0]
    hq, hkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    at = lp["attn"]
    h = rms_norm(x, lp["norm1"]["scale"], eps)
    q = project(at["wq"], h, hq * dh, mats, quant).reshape(s, hq, dh)
    k = project(at["wk"], h, hkv * dh, mats, quant).reshape(s, hkv, dh)
    v = project(at["wv"], h, hkv * dh, mats, quant).reshape(s, hkv, dh)
    q = rope(rms_norm(q, at["q_norm"]["scale"], eps), positions,
             cfg["rope_theta"])
    k = rope(rms_norm(k, at["k_norm"]["scale"], eps), positions,
             cfg["rope_theta"])
    kv_of = np.arange(hq) // (hq // hkv)
    k, v = k[:, kv_of], v[:, kv_of]
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * dh ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    x = x + project(at["wo"], o.reshape(s, hq * dh), cfg["d_model"], mats,
                    quant)
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    ml = lp["mlp"]
    g = project(ml["wg"], h, cfg["d_ff"], mats, quant)
    u = project(ml["wu"], h, cfg["d_ff"], mats, quant)
    return x + project(ml["wd"], jax.nn.silu(g) * u, cfg["d_model"], mats,
                       quant)


def transform_mats(cfg) -> dict:
    """The DCT matrices the ACDC projections need, keyed by size."""
    if cfg["sell"] != "acdc":
        return {}
    d, hd = cfg["d_model"], cfg["n_heads"] * cfg["head_dim"]
    sizes = {op_size(hd, d), op_size(d, cfg["d_ff"]), op_size(cfg["d_ff"], d)}
    return {n: jnp.asarray(dct_matrix(n)) for n in sizes}


def hidden(params, tokens, cfg, mats, quant=None):
    """Final-normed hidden states (S, D) of one sequence."""
    x = params["embed"]["table"][tokens]
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)

    def body(x, lp):
        return layer(lp, x, positions, cfg, mats, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["final_norm"]["scale"], cfg["norm_eps"])


def logits(params, h, quant=None):
    """Tied head: ``h @ E^T`` (rows of ``h`` only)."""
    return _mm(h, params["embed"]["table"].T, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _token_gaps(params, mats, tokens, rows, targets, cfg_items, quant):
    """For each served row ``rows[i]``: ``gap``, how far the target
    token's logit sits below the row's best in row standard deviations,
    and ``rank``, how many tokens have a higher logit, under the float32
    reference; with ``quant``, both for the token that the
    lowered-precision reference puts first instead of the target."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, cfg, mats)[rows]
        lg = logits(params, h)
        if quant is not None:
            hq = hidden(params, tokens, cfg, mats, quant)[rows]
            targets = jnp.argmax(logits(params, hq, quant), axis=-1)
        served = jnp.take_along_axis(lg, targets[:, None], axis=-1)
        gap = (lg.max(-1) - served[:, 0]) / lg.std(-1)
        rank = jnp.sum(lg > served, axis=-1)
        return {"gap": gap, "rank": rank}


def token_gaps(params, mats, tokens, rows, targets, cfg, quant=None):
    return _token_gaps(params, mats, tokens, rows, targets,
                       tuple(sorted(cfg.items())), quant)
