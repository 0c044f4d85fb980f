"""Decoder-only transformer LM assembly.

Covers seven of the ten assigned architectures via config knobs:
deepseek-67b, chatglm3-6b, gemma3-27b, qwen3-1.7b, moonshot-v1-16b-a3b,
deepseek-moe-16b, and the llava-next-34b backbone (vision-stub prefix).

Layer parameters are stacked (leading L axis) and the layer loop is a
``lax.scan`` so the compiled program is O(1) in depth; per-layer
heterogeneity (gemma3's 5:1 local:global windows) rides along as a scanned
int32 array.  ``cfg.remat`` wraps the layer body in ``jax.checkpoint`` with
a policy that saves only the residual stream.
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


def init_layer(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ra, rm = jax.random.split(rng)
    p = {
        "norm1": init_rms_norm(cfg.d_model, dtype),
        "attn": attn_mod.init_attention(ra, cfg, dtype),
        "norm2": init_rms_norm(cfg.d_model, dtype),
    }
    if cfg.n_experts > 0:
        p["moe"] = mlp_mod.init_moe(rm, cfg, dtype)
    else:
        p["mlp"] = mlp_mod.init_mlp(rm, cfg, None, dtype)
    return p


def init(rng: jax.Array, cfg: ModelConfig) -> dict:
    dtype = cfg.param_dtype
    re, rl, rf = jax.random.split(rng, 3)
    layers = jax.vmap(lambda r: init_layer(r, cfg, dtype))(
        jax.random.split(rl, cfg.n_layers))
    params = {
        "embed": embed_init(re, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": init_rms_norm(cfg.d_model, dtype),
    }
    return params


def _layer_fn(layer: dict, x: jax.Array, positions: jax.Array,
              window: jax.Array, cfg: ModelConfig):
    h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
    x = x + attn_mod.attention(layer["attn"], h, positions, window, cfg)
    h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
    if "moe" in layer:
        aux = mlp_mod.moe_aux_loss(layer["moe"], h, cfg)
        x = x + mlp_mod.moe(layer["moe"], h, cfg)
    else:
        aux = jnp.zeros((), jnp.float32)
        x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
    return x, aux


def backbone(params: dict, x: jax.Array, positions: jax.Array,
             cfg: ModelConfig):
    """Run the stacked layers over ``x`` (B, S, D) -> (hidden, mean aux)."""
    windows = jnp.asarray(cfg.layer_windows())

    fn = _layer_fn
    if cfg.remat:
        fn = jax.checkpoint(
            _layer_fn,
            policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(4,),
        )

    def body(carry, xs):
        layer, window = xs
        return fn(layer, carry, positions, window, cfg)

    x, aux = jax.lax.scan(body, x, (params["layers"], windows),
                          unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return x, jnp.mean(aux)


def apply(
    params: dict,
    tokens: jax.Array,                       # (B, S) int32
    cfg: ModelConfig,
    frontend_embeds: Optional[jax.Array] = None,  # (B, P, D) vision stub
) -> jax.Array:
    """Training/prefill forward -> fp32 logits (B, S, V)."""
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens, dtype)
    if frontend_embeds is not None:
        p = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(dtype), x[:, p:]], axis=1)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _ = backbone(params, x, positions, cfg)
    return unembed(params["embed"], x)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    """Next-token cross-entropy; positions with label < 0 are masked."""
    dtype = cfg.compute_dtype
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens, dtype)
    fe = batch.get("frontend_embeds")
    if fe is not None:
        p = fe.shape[1]
        x = jnp.concatenate([fe.astype(dtype), x[:, p:]], axis=1)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, aux = backbone(params, x, positions, cfg)
    logits = unembed(params["embed"], x)
    loss = cross_entropy(logits, batch["labels"], cfg)
    if cfg.n_experts > 0:
        loss = loss + 0.01 * aux
    return loss


# ---------------------------------------------------------------------------
# Decode (one token per step, KV cache).
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return attn_mod.init_kv_cache(cfg, batch, max_len, cfg.n_layers,
                                  cfg.compute_dtype)


def init_cache_paged(cfg: ModelConfig, batch: int, n_blocks: int,
                     block_size: int) -> dict:
    """Paged decode cache: one global page pool shared by all slots
    (``batch`` is unused here — KV is the only state and it is pooled)."""
    del batch
    return attn_mod.init_kv_cache_paged(cfg, n_blocks, block_size,
                                        cfg.n_layers, cfg.compute_dtype)


def prefill(
    params: dict,
    cache: dict,
    tokens: jax.Array,                       # (B, S) right-padded prompts
    cfg: ModelConfig,
    lengths: Optional[jax.Array] = None,     # (B,) valid length per row
    frontend_embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """One lowered forward over the whole prompt -> (logits (B,S,V), cache).

    The layer scan mirrors :func:`backbone` but keeps each layer's K/V and
    scatters them into the cache slab (positions >= the row's length are
    zeroed; see :func:`repro.models.attention.scatter_prefill_kv`).  With
    right padding the causal mask already keeps pad tokens out of every
    real position's context, so ragged batches need no extra masking here.
    """
    b, s = tokens.shape
    smax = cache["k"].shape[2]
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens, dtype)
    if frontend_embeds is not None:
        p = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(dtype), x[:, p:]], axis=1)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    windows = jnp.asarray(cfg.layer_windows())

    def body(carry, xs):
        x = carry
        layer, window = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, k, v = attn_mod.attention_prefill(layer["attn"], h, positions,
                                               window, cfg)
        x = x + out
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        if "moe" in layer:
            x = x + mlp_mod.moe(layer["moe"], h, cfg)
        else:
            x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        ck, cv = attn_mod.scatter_prefill_kv(k, v, lengths, smax)
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], windows),
                                     unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, {"k": new_k.astype(cache["k"].dtype),
                    "v": new_v.astype(cache["v"].dtype)}


def decode_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,        # (B,) current token ids
    position: jax.Array,      # (B,) current position
    cfg: ModelConfig,
) -> Tuple[jax.Array, dict]:
    """One decode step -> (logits (B, V), updated cache)."""
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens[:, None], dtype)  # (B,1,D)
    windows = jnp.asarray(cfg.layer_windows())

    def body(carry, xs):
        x = carry
        layer, window, ck, cv = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, ck, cv = attn_mod.attention_decode(
            layer["attn"], h, ck, cv, position, window, cfg)
        x = x + out
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        if "moe" in layer:
            x = x + mlp_mod.moe(layer["moe"], h, cfg)
        else:
            x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], windows, cache["k"], cache["v"]),
        unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)[:, 0]
    return logits, {"k": new_k, "v": new_v}


def verify_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,        # (B, T) pending token + k draft tokens
    position: jax.Array,      # (B,) first write position per row
    cfg: ModelConfig,
) -> Tuple[jax.Array, dict, None]:
    """Speculative-decode append-and-score: T tokens in ONE lowered pass.

    Returns ``(logits (B, T, V), cache, None)`` — logits at row position
    ``i`` score the token that follows ``tokens[:, i]``, exactly what
    ``decode_step`` would emit feeding the same tokens one at a time.  K/V
    is set-written (:func:`repro.models.attention.attention_verify`), so
    rejected tail positions roll back by rewinding ``position``; the KV
    cache needs no state selection (trailing ``None``).
    """
    dtype = cfg.compute_dtype
    x = embed_lookup(params["embed"], tokens, dtype)          # (B,T,D)
    windows = jnp.asarray(cfg.layer_windows())

    def body(carry, xs):
        x = carry
        layer, window, ck, cv = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, ck, cv = attn_mod.attention_verify(
            layer["attn"], h, ck, cv, position, window, cfg)
        x = x + out
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        if "moe" in layer:
            x = x + mlp_mod.moe(layer["moe"], h, cfg)
        else:
            x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], windows, cache["k"], cache["v"]),
        unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, {"k": new_k, "v": new_v}, None


def _paged_layers(params: dict, x: jax.Array, cache: dict,
                  block_tables: jax.Array, position: jax.Array, attend,
                  cfg: ModelConfig) -> Tuple[jax.Array, dict]:
    """The layer scan of the paged decode and verify steps.

    The whole stacked pools ride in the carry and each layer's attention
    (``attend``, the decode or verify grid) addresses its own pool by
    index, so the donated pools are updated in place: only the new rows
    are written, and no layer's pool is sliced out or stacked back.
    """
    windows = jnp.asarray(cfg.layer_windows())
    layers = jnp.arange(windows.shape[0], dtype=jnp.int32)

    def body(carry, xs):
        x, kp, vp = carry
        layer, window, idx = xs
        h = rms_norm(x, layer["norm1"]["scale"], cfg.norm_eps)
        out, kp, vp = attend(layer["attn"], h, kp, vp, block_tables,
                             position, window, idx, cfg)
        x = x + out
        h = rms_norm(x, layer["norm2"]["scale"], cfg.norm_eps)
        if "moe" in layer:
            x = x + mlp_mod.moe(layer["moe"], h, cfg)
        else:
            x = x + mlp_mod.mlp(layer["mlp"], h, cfg)
        return (x, kp, vp), None

    (x, new_k, new_v), _ = jax.lax.scan(
        body, (x, cache["k_pages"], cache["v_pages"]),
        (params["layers"], windows, layers), unroll=cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return x, {"k_pages": new_k, "v_pages": new_v}


def verify_step_paged(
    params: dict,
    cache: dict,
    tokens: jax.Array,        # (B, T)
    position: jax.Array,      # (B,)
    block_tables: jax.Array,  # (B, MB) int32, -1 = unmapped
    cfg: ModelConfig,
) -> Tuple[jax.Array, dict, None]:
    """Paged twin of :func:`verify_step` (writes through the block table)."""
    x = embed_lookup(params["embed"], tokens, cfg.compute_dtype)  # (B,T,D)
    x, cache = _paged_layers(params, x, cache, block_tables, position,
                             attn_mod.attention_verify_paged, cfg)
    return unembed(params["embed"], x), cache, None


def decode_step_paged(
    params: dict,
    cache: dict,              # {"k_pages", "v_pages"}: (L, NB+1, bs, Hkv, Dh)
    tokens: jax.Array,        # (B,) current token ids
    position: jax.Array,      # (B,) current position
    block_tables: jax.Array,  # (B, MB) int32, -1 = unmapped
    cfg: ModelConfig,
) -> Tuple[jax.Array, dict]:
    """One decode step against the paged KV pool -> (logits (B, V), cache)."""
    x = embed_lookup(params["embed"], tokens[:, None],
                     cfg.compute_dtype)                       # (B,1,D)
    x, cache = _paged_layers(params, x, cache, block_tables, position,
                             attn_mod.attention_decode_paged, cfg)
    return unembed(params["embed"], x)[:, 0], cache
