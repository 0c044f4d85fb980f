"""Zamba2-style hybrid: Mamba2 backbone + a SHARED attention block
(arXiv:2411.15242).

A single transformer block's parameters are reused at every ``attn_every``-th
position in the Mamba2 stack (Zamba's parameter-sharing trick).  As in the
paper, the shared block sees the concatenation of the current hidden state
and the original embedding; we fold that through a 2d->d input projection.

The structure composes with the ACDC SELL naturally: the shared block's
projections and the mamba in/out projections both route through the SELL
factory (shared structured weights = double savings).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import linear
from repro.models import mamba2 as mamba_mod
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


def _n_groups(cfg: ModelConfig):
    k = cfg.attn_every
    full, rem = divmod(cfg.n_layers, k)
    sizes = [k] * full + ([rem] if rem else [])
    return sizes


def init(rng: jax.Array, cfg: ModelConfig) -> dict:
    dtype = cfg.param_dtype
    re, rl, rs, rp = jax.random.split(rng, 4)
    layers = jax.vmap(lambda r: {
        "norm": init_rms_norm(cfg.d_model, dtype),
        "mixer": mamba_mod.init_mamba_block(r, cfg, dtype),
    })(jax.random.split(rl, cfg.n_layers))
    d = cfg.d_model
    shared = {
        "in_proj": linear.linear_init(rp, 2 * d, d, cfg, "shared_in", dtype),
        "norm1": init_rms_norm(d, dtype),
        "attn": attn_mod.init_attention(rs, cfg, dtype),
        "norm2": init_rms_norm(d, dtype),
        "mlp": mlp_mod.init_mlp(jax.random.fold_in(rs, 1), cfg, None, dtype),
    }
    return {
        "embed": embed_init(re, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "shared": shared,
        "final_norm": init_rms_norm(cfg.d_model, dtype),
    }


def _shared_block(shared: dict, x: jax.Array, emb: jax.Array,
                  positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    d = cfg.d_model
    h = linear.linear_apply(shared["in_proj"],
                            jnp.concatenate([x, emb], axis=-1),
                            2 * d, d, cfg, "shared_in")
    a = rms_norm(h, shared["norm1"]["scale"], cfg.norm_eps)
    window = jnp.zeros((), jnp.int32)  # full attention
    h = h + attn_mod.attention(shared["attn"], a, positions, window, cfg)
    m = rms_norm(h, shared["norm2"]["scale"], cfg.norm_eps)
    h = h + mlp_mod.mlp(shared["mlp"], m, cfg)
    return x + h


def apply(params: dict, tokens: jax.Array, cfg: ModelConfig,
          frontend_embeds=None) -> jax.Array:
    dtype = cfg.compute_dtype
    emb = embed_lookup(params["embed"], tokens, dtype)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    fn = mamba_mod._layer_fn
    if cfg.remat:
        fn = jax.checkpoint(mamba_mod._layer_fn,
                            policy=jax.checkpoint_policies.nothing_saveable,
                            static_argnums=(2,))

    def body(carry, layer):
        return fn(layer, carry, cfg), None

    x = emb
    start = 0
    for size in _n_groups(cfg):
        group = jax.tree.map(lambda p: p[start : start + size], params["layers"])
        x, _ = jax.lax.scan(body, x, group, unroll=cfg.scan_unroll)
        x = _shared_block(params["shared"], x, emb, positions, cfg)
        start += size
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return unembed(params["embed"], x)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits = apply(params, batch["tokens"], cfg)
    return cross_entropy(logits, batch["labels"], cfg)


# ---------------------------------------------------------------------------
# Prefill: batched prompt pass filling SSM states + shared-block KV.
# ---------------------------------------------------------------------------

def prefill(params: dict, cache: dict, tokens: jax.Array, cfg: ModelConfig,
            lengths=None, frontend_embeds=None):
    """Mirror of :func:`apply` that keeps every decode cache: per-layer SSM
    and conv states from the mamba groups, plus K/V for each application of
    the shared attention block -> (logits (B,S,V), cache)."""
    b, s = tokens.shape
    smax = cache["attn_k"].shape[2]
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    mask = jnp.arange(s, dtype=jnp.int32)[None, :] < lengths[:, None]
    dtype = cfg.compute_dtype
    emb = embed_lookup(params["embed"], tokens, dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    window = jnp.zeros((), jnp.int32)
    d = cfg.d_model

    def body(carry, layer):
        x = carry
        h = rms_norm(x, layer["norm"]["scale"], cfg.norm_eps)
        y, ssm, conv = mamba_mod.mamba_block_prefill(layer["mixer"], h, cfg,
                                                     mask, lengths)
        return x + y, (ssm, conv)

    x = emb
    start = 0
    new_ssm, new_conv, new_k, new_v = [], [], [], []
    for size in _n_groups(cfg):
        group = jax.tree.map(lambda p: p[start : start + size],
                             params["layers"])
        x, (ssm, conv) = jax.lax.scan(body, x, group, unroll=cfg.scan_unroll)
        new_ssm.append(ssm)
        new_conv.append(conv)
        # shared attention application, keeping its K/V
        h = linear.linear_apply(params["shared"]["in_proj"],
                                jnp.concatenate([x, emb], axis=-1),
                                2 * d, d, cfg, "shared_in")
        a = rms_norm(h, params["shared"]["norm1"]["scale"], cfg.norm_eps)
        out, k, v = attn_mod.attention_prefill(params["shared"]["attn"], a,
                                               positions, window, cfg)
        h = h + out
        m = rms_norm(h, params["shared"]["norm2"]["scale"], cfg.norm_eps)
        h = h + mlp_mod.mlp(params["shared"]["mlp"], m, cfg)
        x = x + h
        ck, cv = attn_mod.scatter_prefill_kv(k, v, lengths, smax)
        new_k.append(ck)
        new_v.append(cv)
        start += size

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, {
        "ssm": jnp.concatenate(new_ssm, axis=0).astype(cache["ssm"].dtype),
        "conv": jnp.concatenate(new_conv, axis=0).astype(cache["conv"].dtype),
        "attn_k": jnp.stack(new_k, axis=0).astype(cache["attn_k"].dtype),
        "attn_v": jnp.stack(new_v, axis=0).astype(cache["attn_v"].dtype),
    }


# ---------------------------------------------------------------------------
# Decode: mamba states + KV caches for each shared-block application.
# ---------------------------------------------------------------------------

#: cache leaves that are truly recurrent (cannot rewind): speculative
#: rollback re-commits them at the accepted length via per-step snapshots,
#: and the paged decode freezes them on stalled (parked) rows.
RECURRENT_CACHE_KEYS = ("ssm", "conv")

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    n_apps = len(_n_groups(cfg))
    cache = mamba_mod.init_ssm_cache(cfg, batch, cfg.n_layers, cfg.compute_dtype)
    kv = attn_mod.init_kv_cache(cfg, batch, max_len, n_apps, cfg.compute_dtype)
    cache["attn_k"] = kv["k"]
    cache["attn_v"] = kv["v"]
    return cache


def init_cache_paged(cfg: ModelConfig, batch: int, n_blocks: int,
                     block_size: int) -> dict:
    """Page only the shared-attention KV — the length-proportional state.
    Mamba SSM/conv state is O(1) per slot regardless of sequence length,
    so it stays dense (there is no worst-case-length slab to reclaim)."""
    n_apps = len(_n_groups(cfg))
    cache = mamba_mod.init_ssm_cache(cfg, batch, cfg.n_layers,
                                     cfg.compute_dtype)
    kv = attn_mod.init_kv_cache_paged(cfg, n_blocks, block_size, n_apps,
                                      cfg.compute_dtype)
    cache["attn_k_pages"] = kv["k_pages"]
    cache["attn_v_pages"] = kv["v_pages"]
    return cache


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                position: jax.Array, cfg: ModelConfig):
    dtype = cfg.compute_dtype
    emb = embed_lookup(params["embed"], tokens[:, None], dtype)

    def body(carry, xs):
        x = carry
        layer, ssm, conv = xs
        h = rms_norm(x, layer["norm"]["scale"], cfg.norm_eps)
        out, ssm, conv = mamba_mod.mamba_block_decode(
            layer["mixer"], h, ssm, conv, cfg)
        return x + out, (ssm, conv)

    x = emb
    start = 0
    new_ssm, new_conv, new_k, new_v = [], [], [], []
    window = jnp.zeros((), jnp.int32)
    for app, size in enumerate(_n_groups(cfg)):
        sl = lambda p: p[start : start + size]
        group = (jax.tree.map(sl, params["layers"]),
                 cache["ssm"][start : start + size],
                 cache["conv"][start : start + size])
        x, (ssm, conv) = jax.lax.scan(body, x, group,
                                      unroll=cfg.scan_unroll)
        new_ssm.append(ssm)
        new_conv.append(conv)
        # shared attention application `app`
        d = cfg.d_model
        h = linear.linear_apply(params["shared"]["in_proj"],
                                jnp.concatenate([x, emb], axis=-1),
                                2 * d, d, cfg, "shared_in")
        a = rms_norm(h, params["shared"]["norm1"]["scale"], cfg.norm_eps)
        out, ck, cv = attn_mod.attention_decode(
            params["shared"]["attn"], a,
            cache["attn_k"][app], cache["attn_v"][app],
            position, window, cfg)
        h = h + out
        m = rms_norm(h, params["shared"]["norm2"]["scale"], cfg.norm_eps)
        h = h + mlp_mod.mlp(params["shared"]["mlp"], m, cfg)
        x = x + h
        new_k.append(ck)
        new_v.append(cv)
        start += size

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)[:, 0]
    return logits, {
        "ssm": jnp.concatenate(new_ssm, axis=0),
        "conv": jnp.concatenate(new_conv, axis=0),
        "attn_k": jnp.stack(new_k, axis=0),
        "attn_v": jnp.stack(new_v, axis=0),
    }


def _verify_impl(params, cache, tokens, position, cfg, attend, kv):
    """Shared speculative append-and-score body (dense / paged shared
    attention differ only in ``attend(app, a, kv) -> (out, kv)``, which
    writes application ``app``'s K/V into the stacked pair ``kv``).
    Returns ``(logits (B,T,V), kv, states)`` with ``states`` the
    per-position recurrent snapshots: each leaf is the cache leaf with a
    ``T+1`` time axis after the batch axis (index j = state after j
    consumed tokens)."""
    dtype = cfg.compute_dtype
    emb = embed_lookup(params["embed"], tokens, dtype)          # (B,T,D)
    d = cfg.d_model

    def body(carry, xs):
        x = carry
        layer, ssm, conv = xs
        h = rms_norm(x, layer["norm"]["scale"], cfg.norm_eps)
        out, ssm_steps, conv_steps = mamba_mod.mamba_block_verify(
            layer["mixer"], h, ssm, conv, cfg)
        return x + out, (ssm_steps, conv_steps)

    x = emb
    start = 0
    new_ssm, new_conv = [], []
    for app, size in enumerate(_n_groups(cfg)):
        sl = lambda p: p[start : start + size]
        group = (jax.tree.map(sl, params["layers"]),
                 cache["ssm"][start : start + size],
                 cache["conv"][start : start + size])
        x, (ssm_steps, conv_steps) = jax.lax.scan(body, x, group,
                                                  unroll=cfg.scan_unroll)
        new_ssm.append(ssm_steps)
        new_conv.append(conv_steps)
        # shared attention application `app`
        h = linear.linear_apply(params["shared"]["in_proj"],
                                jnp.concatenate([x, emb], axis=-1),
                                2 * d, d, cfg, "shared_in")
        a = rms_norm(h, params["shared"]["norm1"]["scale"], cfg.norm_eps)
        out, kv = attend(app, a, kv)
        h = h + out
        m = rms_norm(h, params["shared"]["norm2"]["scale"], cfg.norm_eps)
        h = h + mlp_mod.mlp(params["shared"]["mlp"], m, cfg)
        x = x + h
        start += size

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    states = {"ssm": jnp.concatenate(new_ssm, axis=0),          # (L,B,T+1,..)
              "conv": jnp.concatenate(new_conv, axis=0)}
    return logits, kv, states


def verify_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,        # (B, T) pending token + k draft tokens
    position: jax.Array,      # (B,) first write position per row
    cfg: ModelConfig,
):
    """Speculative append-and-score: shared-attention KV set-written at
    ``position + i`` (rollback = position rewind), Mamba SSM/conv state
    snapshotted per position in ``states`` for accepted-length commit."""
    window = jnp.zeros((), jnp.int32)

    def attend(app, a, kv):
        out, ck, cv = attn_mod.attention_verify(
            params["shared"]["attn"], a, kv[0][app], kv[1][app], position,
            window, cfg)
        return out, (kv[0].at[app].set(ck), kv[1].at[app].set(cv))

    logits, (nk, nv), states = _verify_impl(
        params, cache, tokens, position, cfg, attend,
        (cache["attn_k"], cache["attn_v"]))
    new_cache = {"ssm": states["ssm"][:, :, -1],
                 "conv": states["conv"][:, :, -1],
                 "attn_k": nk, "attn_v": nv}
    return logits, new_cache, states


def verify_step_paged(
    params: dict,
    cache: dict,
    tokens: jax.Array,        # (B, T)
    position: jax.Array,      # (B,)
    block_tables: jax.Array,  # (B, MB)
    cfg: ModelConfig,
):
    """Paged twin of :func:`verify_step`: shared-attention KV set-scattered
    through the block table; SSM/conv snapshots identical to dense."""
    window = jnp.zeros((), jnp.int32)

    def attend(app, a, kv):
        out, kp, vp = attn_mod.attention_verify_paged(
            params["shared"]["attn"], a, *kv, block_tables, position,
            window, jnp.int32(app), cfg)
        return out, (kp, vp)

    logits, (nk, nv), states = _verify_impl(
        params, cache, tokens, position, cfg, attend,
        (cache["attn_k_pages"], cache["attn_v_pages"]))
    new_cache = {"ssm": states["ssm"][:, :, -1],
                 "conv": states["conv"][:, :, -1],
                 "attn_k_pages": nk, "attn_v_pages": nv}
    return logits, new_cache, states


def decode_step_paged(params: dict, cache: dict, tokens: jax.Array,
                      position: jax.Array, block_tables: jax.Array,
                      cfg: ModelConfig):
    """Mirror of :func:`decode_step` with each shared-attention application
    reading/writing its own paged KV pool; SSM/conv state stays dense.

    Rows parked at/beyond the virtual row length (free slots AND slots the
    engine stalled because the page pool ran dry) FREEZE their SSM/conv
    state: a stalled slot's pending token is re-issued once the stall
    clears, and the recurrence — unlike the KV write, which the table
    routes to the trash page — would otherwise consume it twice."""
    dtype = cfg.compute_dtype
    emb = embed_lookup(params["embed"], tokens[:, None], dtype)

    def body(carry, xs):
        x = carry
        layer, ssm, conv = xs
        h = rms_norm(x, layer["norm"]["scale"], cfg.norm_eps)
        out, ssm, conv = mamba_mod.mamba_block_decode(
            layer["mixer"], h, ssm, conv, cfg)
        return x + out, (ssm, conv)

    x = emb
    start = 0
    new_ssm, new_conv = [], []
    kp, vp = cache["attn_k_pages"], cache["attn_v_pages"]
    window = jnp.zeros((), jnp.int32)
    for app, size in enumerate(_n_groups(cfg)):
        sl = lambda p: p[start : start + size]
        group = (jax.tree.map(sl, params["layers"]),
                 cache["ssm"][start : start + size],
                 cache["conv"][start : start + size])
        x, (ssm, conv) = jax.lax.scan(body, x, group,
                                      unroll=cfg.scan_unroll)
        new_ssm.append(ssm)
        new_conv.append(conv)
        # shared attention application `app`
        d = cfg.d_model
        h = linear.linear_apply(params["shared"]["in_proj"],
                                jnp.concatenate([x, emb], axis=-1),
                                2 * d, d, cfg, "shared_in")
        a = rms_norm(h, params["shared"]["norm1"]["scale"], cfg.norm_eps)
        # every application writes its new rows into the whole pools
        out, kp, vp = attn_mod.attention_decode_paged(
            params["shared"]["attn"], a, kp, vp, block_tables, position,
            window, jnp.int32(app), cfg)
        h = h + out
        m = rms_norm(h, params["shared"]["norm2"]["scale"], cfg.norm_eps)
        h = h + mlp_mod.mlp(params["shared"]["mlp"], m, cfg)
        x = x + h
        start += size

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params["embed"], x)[:, 0]
    bs = kp.shape[2]
    parked = (position >= block_tables.shape[1] * bs)[None, :]  # (1, B)
    ssm = jnp.concatenate(new_ssm, axis=0)
    conv = jnp.concatenate(new_conv, axis=0)
    return logits, {
        "ssm": jnp.where(parked[..., None, None, None], cache["ssm"], ssm),
        "conv": jnp.where(parked[..., None, None], cache["conv"], conv),
        "attn_k_pages": kp,
        "attn_v_pages": vp,
    }
