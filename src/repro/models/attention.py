"""Grouped-query attention with RoPE variants, sliding windows and KV cache.

One implementation serves: full attention (deepseek/llava), GQA with few KV
heads (chatglm3 kv=2), qk-norm (qwen3), partial-rotary "2d" RoPE (chatglm3),
per-layer local/global windows (gemma3 5:1), logit soft-capping, and the
cross-attention used by the encoder-decoder (seamless).

Train path computes full (Sq, Sk) score tiles with a dynamic causal+window
mask so heterogeneous layer patterns survive ``lax.scan``.  Decode path
appends one token to the cache and attends over the prefix.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import paged_attn as paged_attn_mod
from repro.models import linear
from repro.models.common import (
    ModelConfig,
    apply_rope,
    causal_window_mask,
    init_rms_norm,
    rms_norm,
)


def init_attention(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32,
                   cross: bool = False) -> dict:
    dh = cfg.head_dim_
    d = cfg.d_model
    rq, rk, rv, ro = jax.random.split(rng, 4)
    p = {
        "wq": linear.linear_init(rq, d, cfg.n_heads * dh, cfg, "attn_qkv", dtype),
        "wk": linear.linear_init(rk, d, cfg.n_kv_heads * dh, cfg, "attn_qkv", dtype),
        "wv": linear.linear_init(rv, d, cfg.n_kv_heads * dh, cfg, "attn_qkv", dtype),
        "wo": linear.linear_init(ro, cfg.n_heads * dh, d, cfg, "attn_out", dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(dh, dtype)
        p["k_norm"] = init_rms_norm(dh, dtype)
    return p


def _project_qkv(params: dict, xq: jax.Array, xkv: jax.Array,
                 cfg: ModelConfig):
    dh = cfg.head_dim_
    d = cfg.d_model
    q = linear.linear_apply(params["wq"], xq, d, cfg.n_heads * dh, cfg, "attn_qkv")
    k = linear.linear_apply(params["wk"], xkv, d, cfg.n_kv_heads * dh, cfg, "attn_qkv")
    v = linear.linear_apply(params["wv"], xkv, d, cfg.n_kv_heads * dh, cfg, "attn_qkv")
    q = q.reshape(*xq.shape[:-1], cfg.n_heads, dh)
    k = k.reshape(*xkv.shape[:-1], cfg.n_kv_heads, dh)
    v = v.reshape(*xkv.shape[:-1], cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"]["scale"], cfg.norm_eps)
    return q, k, v


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
          mask: Optional[jax.Array], cfg: ModelConfig) -> jax.Array:
    """q: (B, Sq, Hq, Dh), k/v: (B, Sk, Hkv, Dh) -> (B, Sq, Hq, Dh)."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores * (dh ** -0.5)
    if cfg.attn_logit_softcap > 0:
        cap = cfg.attn_logit_softcap
        scores = cap * jnp.tanh(scores / cap)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                  positions: jax.Array, window: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Flash-structured attention: online softmax over KV chunks.

    Never materializes the (Sq, Sk) score matrix — live memory is
    O(Sq * chunk) — which removes the dominant HBM-traffic term of vanilla
    attention at training/prefill sequence lengths (see EXPERIMENTS.md
    section Perf, hillclimb #1).  Same math as :func:`_sdpa` including the
    causal+window mask and logit soft-capping; numerics verified by
    tests/test_attention_impls.py.
    """
    b, sq, hq, dh = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    group = hq // hkv
    chunk = min(cfg.attn_chunk, sk)
    n_chunks = sk // chunk if sk % chunk == 0 else -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = q.reshape(b, sq, hkv, group, dh).astype(jnp.float32)
    scale = dh ** -0.5
    q_pos = positions            # (B, Sq)
    kpos_full = jnp.arange(n_chunks * chunk, dtype=jnp.int32)

    def body(carry, idx):
        m, l, acc = carry        # m,l: (B,Hkv,G,Sq); acc: (B,Hkv,G,Sq,Dh)
        start = idx * chunk
        kc = jax.lax.dynamic_slice_in_dim(k, start, chunk, 1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, chunk, 1)
        kp = jax.lax.dynamic_slice_in_dim(kpos_full, start, chunk, 0)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                       kc.astype(jnp.float32)) * scale
        if cfg.attn_logit_softcap > 0:
            cap = cfg.attn_logit_softcap
            s = cap * jnp.tanh(s / cap)
        valid = kp < sk  # (Ck,) — mask the padded tail chunk
        msk = causal_window_mask(q_pos, kp[None, :], window)  # (B, Sq, Ck)
        msk = jnp.logical_and(msk, valid[None, None, :])
        s = jnp.where(msk[:, None, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, group, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), jnp.arange(n_chunks, dtype=jnp.int32),
        unroll=cfg.scan_unroll)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dh)
    return out.astype(q.dtype)


def attention_prefill(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    window: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal self-attention over a whole prompt, keeping K/V for the cache.

    x: (B, S, D) -> (out (B, S, D), k, v (B, S, Hkv, Dh)).  The returned
    k is post-RoPE — exactly the layout :func:`attention_decode` appends,
    so a prefill scatter followed by decode steps is state-identical to
    feeding the prompt token-by-token.
    """
    q, k, v = _project_qkv(params, x, x, cfg)
    q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
    if cfg.attn_impl == "chunked":
        out = _sdpa_chunked(q, k, v, positions, window, cfg)
    else:
        mask = causal_window_mask(positions, positions, window)
        out = _sdpa(q, k, v, mask, cfg)
    dh = cfg.head_dim_
    out = out.reshape(*x.shape[:-1], cfg.n_heads * dh)
    out = linear.linear_apply(params["wo"], out, cfg.n_heads * dh,
                              cfg.d_model, cfg, "attn_out")
    return out, k, v


def scatter_prefill_kv(
    k: jax.Array,                    # (B, S, Hkv, Dh) post-RoPE prompt keys
    v: jax.Array,
    lengths: jax.Array,              # (B,) valid prompt length per row
    max_len: int,
) -> Tuple[jax.Array, jax.Array]:
    """Lay prompt K/V into a fresh (B, max_len, Hkv, Dh) cache slab.

    Positions >= the row's length are ZERO — :func:`attention_decode`
    appends additively (cache + onehot * k), so any stale value at a
    future position would corrupt the first decode write there.  The slab
    overwrites the slot's previous occupant entirely (continuous batching
    reuses slots without a separate reset pass).
    """
    b, s = k.shape[:2]
    pad = ((0, 0), (0, max_len - s), (0, 0), (0, 0))
    valid = (jnp.arange(max_len, dtype=jnp.int32)[None, :]
             < lengths[:, None])[:, :, None, None]
    return (jnp.where(valid, jnp.pad(k, pad), 0),
            jnp.where(valid, jnp.pad(v, pad), 0))


def attention(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    window: jax.Array,
    cfg: ModelConfig,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    kv_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Self-attention (kv=None) or cross-attention (kv = encoder k/v source).

    x: (B, S, D); positions: (B, S); window: traced int32 scalar (0=global).
    """
    if kv is None:
        out, _, _ = attention_prefill(params, x, positions, window, cfg)
        return out
    # cross-attention: no RoPE, full visibility over encoder states
    q, k, v = _project_qkv(params, x, kv[0], cfg)
    out = _sdpa(q, k, v, None, cfg)
    dh = cfg.head_dim_
    out = out.reshape(*x.shape[:-1], cfg.n_heads * dh)
    return linear.linear_apply(params["wo"], out, cfg.n_heads * dh,
                               cfg.d_model, cfg, "attn_out")


# ---------------------------------------------------------------------------
# Decode path (one new token against a KV cache).
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: int, dtype) -> dict:
    dh = cfg.head_dim_
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, dh)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def init_kv_cache_paged(cfg: ModelConfig, n_blocks: int, block_size: int,
                        n_layers: int, dtype) -> dict:
    """Global page pool replacing the per-slot ``max_len`` slabs.

    One extra physical page (index ``n_blocks``) is the write sink: decode
    writes from parked/stalled batch rows are routed there instead of into
    a mapped page, and nothing ever reads it back.  Block ids and per-slot
    tables are owned by :class:`repro.serving.blocks.BlockAllocator`.
    """
    dh = cfg.head_dim_
    shape = (n_layers, n_blocks + 1, block_size, cfg.n_kv_heads, dh)
    return {
        "k_pages": jnp.zeros(shape, dtype),
        "v_pages": jnp.zeros(shape, dtype),
    }


def scatter_prefill_pages(
    pages: jax.Array,                # (L, NB+1, bs, ...) page pool
    slab: jax.Array,                 # (L, 1, S, ...) dense prefill slab
    phys_blocks: jax.Array,          # (S // bs,) physical page per block
) -> jax.Array:
    """Paged prefill scatter: lay a batch-1 dense KV slab into the pool.

    ``phys_blocks`` is the slot's block-table row with unmapped entries
    already routed to the trash page, so blocks beyond the prompt write
    harmlessly into the sink.  Whole pages are overwritten (zeros beyond
    the prompt length included), so a remapped page needs no reset pass.
    """
    n_layers = slab.shape[0]
    s = slab.shape[2]
    bs = pages.shape[2]
    vals = slab[:, 0].reshape(n_layers, s // bs, bs, *slab.shape[3:])
    return pages.at[:, phys_blocks].set(vals.astype(pages.dtype))


def _attention_paged(
    params: dict,
    x: jax.Array,                   # (B, T, D); T=1 decode, T=k+1 verify
    k_pages: jax.Array,             # (L, NB+1, bs, Hkv, Dh) — every layer
    v_pages: jax.Array,
    block_tables: jax.Array,        # (B, MB) int32, -1 = unmapped
    position: jax.Array,            # (B,) first write index per row
    window: jax.Array,
    layer: jax.Array,               # traced int32 scalar: this layer's pool
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared paged decode/verify body — decode is the T=1 case.

    The T new tokens' K/V is set-scattered into their tail pages of pool
    ``layer`` (``block_tables[b, pos // bs]``, offset ``pos % bs``);
    tokens whose page is unmapped or whose position is at/beyond the
    virtual row length (parked/stalled slots) write to the trash page
    instead.  The whole stacked pools come in and go out, so a layer
    loop carries them and only the new rows are written: no layer's
    pool is sliced out or stacked back.

    Attention dispatches through ``ops.paged_attn_route`` (the single
    call site for both grid shapes): in budget on a real device — or
    under ``paged_attn.FORCE_FUSED`` — the fused Pallas kernel walks the
    block table and streams only mapped, in-frontier pages (O(len)
    bytes/slot); otherwise this gather fallback materializes the
    ``(B, MB*bs, ...)`` virtual view page-wise through the table
    (unmapped entries read page 0, whose stale contents sit beyond the
    causal frontier and are masked) and runs plain SDPA.  Greedy streams
    are identical either way (pinned by tests/test_paged_attention.py).
    """
    b, t, _ = x.shape
    n_pages, bs = k_pages.shape[1], k_pages.shape[2]
    mb = block_tables.shape[1]
    virtual = mb * bs
    dh = cfg.head_dim_
    q, k, v = _project_qkv(params, x, x, cfg)
    pos = position[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (B,T)
    q = apply_rope(q, pos, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_fraction, cfg.rope_theta)

    blk = ops.paged_attn_route(cfg.n_kv_heads, dh,
                               cfg.n_heads // cfg.n_kv_heads, t, bs,
                               k_pages.dtype)
    if blk is not None:
        pc, bh = blk
        out, k_pages, v_pages = paged_attn_mod.paged_attention(
            q, k, v, k_pages, v_pages, block_tables, position, window,
            layer, softcap=cfg.attn_logit_softcap, page_chunk=pc,
            head_block=bh, interpret=ops.interpret_mode())
    else:
        blk_idx = jnp.minimum(pos // bs, mb - 1)                       # (B,T)
        phys = jnp.take_along_axis(block_tables, blk_idx, axis=1)      # (B,T)
        writable = jnp.logical_and(phys >= 0, pos < virtual)
        phys = jnp.where(writable, phys, n_pages - 1)                  # sink
        off = pos % bs
        k_pages = k_pages.at[layer, phys, off].set(k.astype(k_pages.dtype))
        v_pages = v_pages.at[layer, phys, off].set(v.astype(v_pages.dtype))

        tbl = jnp.where(block_tables >= 0, block_tables, 0)            # (B,MB)
        ck = k_pages[layer, tbl].reshape(b, virtual, *k_pages.shape[3:])
        cv = v_pages[layer, tbl].reshape(b, virtual, *v_pages.shape[3:])
        k_pos = jnp.arange(virtual, dtype=jnp.int32)[None, :]
        mask = causal_window_mask(pos, k_pos, window)                  # (B,T,V)
        out = _sdpa(q, ck, cv, mask, cfg)
    out = out.reshape(b, t, cfg.n_heads * dh)
    out = linear.linear_apply(params["wo"], out, cfg.n_heads * dh,
                              cfg.d_model, cfg, "attn_out")
    return out, k_pages, v_pages


def attention_decode_paged(
    params: dict,
    x: jax.Array,                   # (B, 1, D)
    k_pages: jax.Array,             # (L, NB+1, bs, Hkv, Dh) — every layer
    v_pages: jax.Array,
    block_tables: jax.Array,        # (B, MB) int32, -1 = unmapped
    position: jax.Array,            # (B,) current index
    window: jax.Array,
    layer: jax.Array,               # traced int32 scalar
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Paged twin of :func:`attention_decode`: the T=1 grid shape of
    :func:`_attention_paged`."""
    return _attention_paged(params, x, k_pages, v_pages, block_tables,
                            position, window, layer, cfg)


def attention_verify(
    params: dict,
    x: jax.Array,                   # (B, T, D) — pending token + k drafts
    cache_k: jax.Array,             # (B, Smax, Hkv, Dh) — this layer's slice
    cache_v: jax.Array,
    position: jax.Array,            # (B,) first write index per row
    window: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Append-and-score T tokens against the dense cache in one pass.

    The speculative-decode verify primitive: row ``b``'s tokens occupy
    positions ``position[b] .. position[b] + T - 1``.  K/V is written with
    ``set`` (NOT the additive decode scatter), so a later rollback is just
    a position rewind — stale values beyond the new frontier sit past the
    causal mask and are overwritten exactly by the next set-write.  Rows
    whose position is parked (at/beyond ``Smax``) write nothing (the
    scatter drops out-of-bounds indices).  Per position the math matches
    :func:`attention_decode` reduction-for-reduction, so greedy argmax
    agreement with token-at-a-time decode is exact.
    """
    b, t, _ = x.shape
    smax = cache_k.shape[1]
    q, k, v = _project_qkv(params, x, x, cfg)
    pos = position[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (B,T)
    q = apply_rope(q, pos, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_fraction, cfg.rope_theta)
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]                     # (B,1)
    cache_k = cache_k.at[bidx, pos].set(k.astype(cache_k.dtype), mode="drop")
    cache_v = cache_v.at[bidx, pos].set(v.astype(cache_v.dtype), mode="drop")
    k_pos = jnp.arange(smax, dtype=jnp.int32)[None, :]                 # (1,Smax)
    mask = causal_window_mask(pos, k_pos, window)                      # (B,T,Smax)
    out = _sdpa(q, cache_k, cache_v, mask, cfg)
    dh = cfg.head_dim_
    out = out.reshape(b, t, cfg.n_heads * dh)
    out = linear.linear_apply(params["wo"], out, cfg.n_heads * dh,
                              cfg.d_model, cfg, "attn_out")
    return out, cache_k, cache_v


def attention_verify_paged(
    params: dict,
    x: jax.Array,                   # (B, T, D)
    k_pages: jax.Array,             # (L, NB+1, bs, Hkv, Dh) — every layer
    v_pages: jax.Array,
    block_tables: jax.Array,        # (B, MB) int32, -1 = unmapped
    position: jax.Array,            # (B,) first write index per row
    window: jax.Array,
    layer: jax.Array,               # traced int32 scalar
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Paged twin of :func:`attention_verify`: the T=k+1 grid shape of
    :func:`_attention_paged`.

    The engine pre-maps pages for the whole verify window
    (``ensure_range``) or parks the row; unmapped or parked positions
    route to the trash page.  Rollback is a position rewind plus
    returning over-mapped tail pages — page contents are never cleaned,
    exactly like the single-token decode path.
    """
    return _attention_paged(params, x, k_pages, v_pages, block_tables,
                            position, window, layer, cfg)


def attention_decode(
    params: dict,
    x: jax.Array,                   # (B, 1, D)
    cache_k: jax.Array,             # (B, Smax, Hkv, Dh) — this layer's slice
    cache_v: jax.Array,
    position: jax.Array,            # (B,) current index
    window: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (out (B,1,D), new_cache_k, new_cache_v)."""
    b, _, _ = x.shape
    smax = cache_k.shape[1]
    q, k, v = _project_qkv(params, x, x, cfg)
    pos2 = position[:, None]  # (B,1)
    q = apply_rope(q, pos2, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, pos2, cfg.rope_fraction, cfg.rope_theta)
    # scatter the new k/v at `position`
    onehot = jax.nn.one_hot(position, smax, dtype=k.dtype)  # (B, Smax)
    cache_k = cache_k + onehot[:, :, None, None] * k
    cache_v = cache_v + onehot[:, :, None, None] * v
    k_pos = jnp.arange(smax, dtype=jnp.int32)[None, :]  # (1, Smax)
    # causal also excludes unwritten cache slots (they sit beyond `position`)
    mask = causal_window_mask(pos2, k_pos, window)      # (B, 1, Smax)
    out = _sdpa(q, cache_k, cache_v, mask, cfg)
    dh = cfg.head_dim_
    out = out.reshape(b, 1, cfg.n_heads * dh)
    out = linear.linear_apply(params["wo"], out, cfg.n_heads * dh,
                              cfg.d_model, cfg, "attn_out")
    return out, cache_k, cache_v
