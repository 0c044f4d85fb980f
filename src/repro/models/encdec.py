"""Encoder-decoder transformer (SeamlessM4T-v2 text/audio backbone).

Per the assignment, the modality frontend is a STUB: ``input_specs`` feeds
precomputed audio frame embeddings (B, frames, D) into the encoder; the
decoder is a causal transformer with cross-attention over encoder states.

Decode path caches both the decoder self-attention KV and the (static)
cross-attention KV computed once from the encoder output at prefill.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models.common import (
    ModelConfig,
    cross_entropy,
    embed_init,
    embed_lookup,
    init_rms_norm,
    rms_norm,
    unembed,
)


def init_encoder_layer(rng, cfg, dtype):
    ra, rm = jax.random.split(rng)
    return {
        "norm1": init_rms_norm(cfg.d_model, dtype),
        "attn": attn_mod.init_attention(ra, cfg, dtype),
        "norm2": init_rms_norm(cfg.d_model, dtype),
        "mlp": mlp_mod.init_mlp(rm, cfg, None, dtype),
    }


def init_decoder_layer(rng, cfg, dtype):
    ra, rx, rm = jax.random.split(rng, 3)
    return {
        "norm1": init_rms_norm(cfg.d_model, dtype),
        "attn": attn_mod.init_attention(ra, cfg, dtype),
        "norm_x": init_rms_norm(cfg.d_model, dtype),
        "cross": attn_mod.init_attention(rx, cfg, dtype, cross=True),
        "norm2": init_rms_norm(cfg.d_model, dtype),
        "mlp": mlp_mod.init_mlp(rm, cfg, None, dtype),
    }


def init(rng: jax.Array, cfg: ModelConfig) -> dict:
    dtype = cfg.param_dtype
    re, renc, rdec = jax.random.split(rng, 3)
    n_enc = cfg.n_encoder_layers or cfg.n_layers
    return {
        "embed": embed_init(re, cfg.vocab_size, cfg.d_model, dtype),
        "encoder": jax.vmap(lambda r: init_encoder_layer(r, cfg, dtype))(
            jax.random.split(renc, n_enc)),
        "decoder": jax.vmap(lambda r: init_decoder_layer(r, cfg, dtype))(
            jax.random.split(rdec, cfg.n_layers)),
        "enc_norm": init_rms_norm(cfg.d_model, dtype),
        "final_norm": init_rms_norm(cfg.d_model, dtype),
    }


def _enc_layer(layer, x, positions, cfg):
    # bidirectional: no causal mask -> emulate with window=0 and full mask
    h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
    # bidirectional self-attention: use cross-attention path (mask=None)
    x = x + attn_mod.attention(layer["attn"], h, positions, jnp.zeros((), jnp.int32),
                               cfg, kv=(h,), kv_positions=positions)
    h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
    return x + mlp_mod.mlp(layer["mlp"], h, cfg), None


def encode(params: dict, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """frames: (B, F, D) stub audio embeddings -> encoder states."""
    b, f, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None], (b, f))

    fn = _enc_layer
    if cfg.remat:
        fn = jax.checkpoint(_enc_layer,
                            policy=jax.checkpoint_policies.nothing_saveable,
                            static_argnums=(3,))

    def body(carry, layer):
        y, _ = fn(layer, carry, positions, cfg)
        return y, None

    x, _ = jax.lax.scan(body, frames.astype(cfg.compute_dtype),
                        params["encoder"], unroll=cfg.scan_unroll)
    return rms_norm(x, params["enc_norm"]["scale"], cfg.norm_eps)


def _dec_layer(layer, x, enc, positions, cfg):
    window = jnp.zeros((), jnp.int32)
    h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
    x = x + attn_mod.attention(layer["attn"], h, positions, window, cfg)
    h = rms_norm(x, layer["norm_x"]["scale"], cfg.norm_eps)
    x = x + attn_mod.attention(layer["cross"], h, positions, window, cfg,
                               kv=(enc,))
    h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
    return x + mlp_mod.mlp(layer["mlp"], h, cfg)


def apply(params: dict, tokens: jax.Array, cfg: ModelConfig,
          frontend_embeds: Optional[jax.Array] = None) -> jax.Array:
    """tokens: (B, S) decoder input; frontend_embeds: (B, F, D) audio stub."""
    assert frontend_embeds is not None, "enc-dec needs frontend embeddings"
    enc = encode(params, frontend_embeds, cfg)
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens, dtype)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    fn = _dec_layer
    if cfg.remat:
        fn = jax.checkpoint(_dec_layer,
                            policy=jax.checkpoint_policies.nothing_saveable,
                            static_argnums=(4,))

    def body(carry, layer):
        return fn(layer, carry, enc, positions, cfg), None

    x, _ = jax.lax.scan(body, x, params["decoder"],
                        unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return unembed(params["embed"], x)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits = apply(params, batch["tokens"], cfg, batch["frontend_embeds"])
    return cross_entropy(logits, batch["labels"], cfg)


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    kv = attn_mod.init_kv_cache(cfg, batch, max_len, cfg.n_layers,
                                cfg.compute_dtype)
    frames = cfg.n_frontend_tokens or 128
    dh = cfg.head_dim_
    return {
        "k": kv["k"],
        "v": kv["v"],
        # cross-attention KV, filled at prefill from encoder states
        "xk": jnp.zeros((cfg.n_layers, batch, frames, cfg.n_kv_heads, dh),
                        cfg.compute_dtype),
        "xv": jnp.zeros((cfg.n_layers, batch, frames, cfg.n_kv_heads, dh),
                        cfg.compute_dtype),
    }


def init_cache_paged(cfg: ModelConfig, batch: int, n_blocks: int,
                     block_size: int) -> dict:
    """Paged decoder self-attention KV.  The cross KV stays dense: it is
    frames-sized per slot (static, written once at prefill), so there is
    no ragged-length waste to reclaim by paging it."""
    kv = attn_mod.init_kv_cache_paged(cfg, n_blocks, block_size,
                                      cfg.n_layers, cfg.compute_dtype)
    frames = cfg.n_frontend_tokens or 128
    dh = cfg.head_dim_
    return {
        "k_pages": kv["k_pages"],
        "v_pages": kv["v_pages"],
        "xk": jnp.zeros((cfg.n_layers, batch, frames, cfg.n_kv_heads, dh),
                        cfg.compute_dtype),
        "xv": jnp.zeros((cfg.n_layers, batch, frames, cfg.n_kv_heads, dh),
                        cfg.compute_dtype),
    }


def prefill_cross(params: dict, cache: dict, frames: jax.Array,
                  cfg: ModelConfig) -> dict:
    """Run the encoder once and precompute per-layer cross KV."""
    enc = encode(params, frames, cfg)
    dh = cfg.head_dim_

    def one_layer(layer):
        k = attn_mod.linear.linear_apply(
            layer["cross"]["wk"], enc, cfg.d_model,
            cfg.n_kv_heads * dh, cfg, "attn_qkv")
        v = attn_mod.linear.linear_apply(
            layer["cross"]["wv"], enc, cfg.d_model,
            cfg.n_kv_heads * dh, cfg, "attn_qkv")
        k = k.reshape(*enc.shape[:-1], cfg.n_kv_heads, dh)
        v = v.reshape(*enc.shape[:-1], cfg.n_kv_heads, dh)
        return k, v

    xk, xv = jax.vmap(one_layer)(params["decoder"])
    return {**cache, "xk": xk.astype(cfg.compute_dtype),
            "xv": xv.astype(cfg.compute_dtype)}


def prefill(params: dict, cache: dict, tokens: jax.Array, cfg: ModelConfig,
            lengths=None, frontend_embeds=None):
    """Batched decoder prompt pass -> (logits (B,S,V), cache).

    If ``frontend_embeds`` is given the encoder runs first
    (:func:`prefill_cross`); otherwise the cache's existing cross K/V is
    used — the same precomputed layout :func:`decode_step` reads, so
    prefill-then-decode agrees with token-at-a-time decode exactly.
    """
    if frontend_embeds is not None:
        cache = prefill_cross(params, cache, frontend_embeds, cfg)
    b, s = tokens.shape
    smax = cache["k"].shape[2]
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens, dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    window = jnp.zeros((), jnp.int32)
    dh = cfg.head_dim_

    def body(carry, xs):
        x = carry
        layer, xk, xv = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, k, v = attn_mod.attention_prefill(layer["attn"], h, positions,
                                               window, cfg)
        x = x + out
        # cross-attention against the precomputed encoder KV
        h = rms_norm(x, layer["norm_x"]["scale"], cfg.norm_eps)
        x = x + _cross_attend(layer, h, xk, xv, cfg)
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        ck, cv = attn_mod.scatter_prefill_kv(k, v, lengths, smax)
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["decoder"], cache["xk"], cache["xv"]),
        unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, {**cache, "k": new_k.astype(cache["k"].dtype),
                    "v": new_v.astype(cache["v"].dtype)}


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                position: jax.Array, cfg: ModelConfig):
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens[:, None], dtype)
    window = jnp.zeros((), jnp.int32)

    def body(carry, xs):
        x = carry
        layer, ck, cv, xk, xv = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, ck, cv = attn_mod.attention_decode(
            layer["attn"], h, ck, cv, position, window, cfg)
        x = x + out
        # cross-attention against the precomputed encoder KV
        h = rms_norm(x, layer["norm_x"]["scale"], cfg.norm_eps)
        x = x + _cross_attend(layer, h, xk, xv, cfg)
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        return x, (ck, cv)

    x, (nk, nv) = jax.lax.scan(
        body, x,
        (params["decoder"], cache["k"], cache["v"], cache["xk"], cache["xv"]),
        unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)[:, 0]
    return logits, {**cache, "k": nk, "v": nv}


def _cross_attend(layer: dict, h: jax.Array, xk: jax.Array, xv: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Cross-attention of (B, T, D) queries over the precomputed encoder
    KV — shared by the decode, verify and prefill bodies (T = 1, k+1, S)."""
    dh = cfg.head_dim_
    q = attn_mod.linear.linear_apply(
        layer["cross"]["wq"], h, cfg.d_model, cfg.n_heads * dh,
        cfg, "attn_qkv").reshape(*h.shape[:-1], cfg.n_heads, dh)
    out = attn_mod._sdpa(q, xk, xv, None, cfg)
    out = out.reshape(*h.shape[:-1], cfg.n_heads * dh)
    return attn_mod.linear.linear_apply(
        layer["cross"]["wo"], out, cfg.n_heads * dh, cfg.d_model,
        cfg, "attn_out")


def verify_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,        # (B, T) pending token + k draft tokens
    position: jax.Array,      # (B,) first write position per row
    cfg: ModelConfig,
):
    """Speculative append-and-score (see transformer.verify_step): decoder
    self-attention KV set-written at ``position + i``, cross KV read-only."""
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens, dtype)
    window = jnp.zeros((), jnp.int32)

    def body(carry, xs):
        x = carry
        layer, ck, cv, xk, xv = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, ck, cv = attn_mod.attention_verify(
            layer["attn"], h, ck, cv, position, window, cfg)
        x = x + out
        h = rms_norm(x, layer["norm_x"]["scale"], cfg.norm_eps)
        x = x + _cross_attend(layer, h, xk, xv, cfg)
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        return x, (ck, cv)

    x, (nk, nv) = jax.lax.scan(
        body, x,
        (params["decoder"], cache["k"], cache["v"], cache["xk"], cache["xv"]),
        unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, {**cache, "k": nk, "v": nv}, None


def _paged_layers(params: dict, x: jax.Array, cache: dict,
                  block_tables: jax.Array, position: jax.Array, attend,
                  cfg: ModelConfig):
    """The decoder scan of the paged decode and verify steps: the stacked
    self-attention pools ride in the carry and each layer's ``attend``
    writes only its new rows into them, by layer index; the precomputed
    cross KV rides along dense and untouched as ``xs``."""
    window = jnp.zeros((), jnp.int32)
    layers = jnp.arange(cache["xk"].shape[0], dtype=jnp.int32)

    def body(carry, xs):
        x, kp, vp = carry
        layer, xk, xv, idx = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, kp, vp = attend(layer["attn"], h, kp, vp, block_tables,
                             position, window, idx, cfg)
        x = x + out
        # cross-attention against the precomputed encoder KV
        h = rms_norm(x, layer["norm_x"]["scale"], cfg.norm_eps)
        x = x + _cross_attend(layer, h, xk, xv, cfg)
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        return (x, kp, vp), None

    (x, nk, nv), _ = jax.lax.scan(
        body, (x, cache["k_pages"], cache["v_pages"]),
        (params["decoder"], cache["xk"], cache["xv"], layers),
        unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return x, {**cache, "k_pages": nk, "v_pages": nv}


def verify_step_paged(
    params: dict,
    cache: dict,
    tokens: jax.Array,        # (B, T)
    position: jax.Array,      # (B,)
    block_tables: jax.Array,  # (B, MB)
    cfg: ModelConfig,
):
    """Paged twin of :func:`verify_step`; cross KV stays dense."""
    x = embed_lookup(params["embed"], tokens, cfg.compute_dtype)
    x, cache = _paged_layers(params, x, cache, block_tables, position,
                             attn_mod.attention_verify_paged, cfg)
    return unembed(params["embed"], x), cache, None


def decode_step_paged(params: dict, cache: dict, tokens: jax.Array,
                      position: jax.Array, block_tables: jax.Array,
                      cfg: ModelConfig):
    """Mirror of :func:`decode_step` with self-attention KV paged; the
    precomputed cross KV rides along dense and untouched."""
    x = embed_lookup(params["embed"], tokens[:, None], cfg.compute_dtype)
    x, cache = _paged_layers(params, x, cache, block_tables, position,
                             attn_mod.attention_decode_paged, cfg)
    return unembed(params["embed"], x)[:, 0], cache
