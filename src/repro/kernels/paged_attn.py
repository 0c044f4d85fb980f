"""Fused paged-attention decode/verify kernel — the block-table walk.

The gather path in ``models/attention.py`` materializes every slot's
ENTIRE virtual K/V view ``(B, MB*bs, Hkv, Dh)`` via ``k_pages[tbl]``
before SDPA, so a slot 10 tokens into a 4096-token table reads ~400x
the bytes it needs.  This kernel (vLLM-style) never builds that view:

* each grid program ``(slot, head-block)`` walks its slot's block table
  (scalar-prefetched into SMEM) and DMAs only the *mapped, in-frontier*
  pages of K/V from the pool (``pl.ANY`` memory space) into a VMEM
  chunk buffer, ``page_chunk`` pages per round;
* attention runs as an online softmax (flash-style running max m and
  denominator l in fp32) per chunk, with the causal/window mask computed
  from ``position`` — chunks wholly outside a sliding window are skipped
  via a per-row start chunk, and streaming stops at the slot's frontier;
* the T new tokens' K/V (T=1 decode, T=k+1 speculative verify — one
  body, two grid shapes) are set-scattered into their tail pages by
  in-kernel DMA on the input/output-aliased pool, then attended straight
  from VMEM (so the streamed prefix never needs a read-after-write of
  the pool).  Parked/stalled rows and positions at/beyond the virtual
  row route to the trash page exactly like the gather path's scatter;
* the pool is the whole stacked ``(L, NB+1, bs, Hkv, Dh)`` cache and
  the layer one more scalar-prefetch operand, so a layer loop carries
  the aliased pool and writes only the new rows: no layer's pages are
  sliced out of the cache or stacked back into it.

Per slot per layer the streamed bytes are ``ceil(len/bs) * bs * bh-slice
* Dh * 2 * itemsize`` — O(len), independent of the table capacity MB —
vs the gather's fixed ``MB * bs * Hkv * Dh * 2 * itemsize``.

Mask contract (must mirror ``causal_window_mask`` + the gather's
routing, pinned by tests/test_paged_attention.py):

* streamed keys: ``kpos < position`` and, for ``window > 0``,
  ``qpos - kpos < window``; unmapped table entries read page 0 exactly
  like the gather's ``where(tbl >= 0, tbl, 0)`` routing (the allocator
  guarantees pages below the frontier are mapped);
* new-token keys: ``kpos <= qpos``, ``kpos < virtual`` (tokens written
  to the trash page are not readable) and the window;
* rows parked at/beyond the virtual length stream nothing; their output
  is a uniform average of the new tokens (all-masked online softmax) —
  junk the engine discards, where the gather path computes whole-table
  garbage junk instead.  The other out-of-contract divergence: a row
  whose WRITE page is unmapped below the virtual frontier attends its
  real new token here, while the gather re-reads the stale routed-page
  value (its write went to trash).  The engine never decodes such a row
  — ``_ensure_blocks`` parks it — so in-contract streams are identical.

Routing lives in ``ops.paged_attn_route`` (counters + budget), block
sizes in :func:`pick_block` / the ``autotune.py`` ``paged_attn``
direction; the VMEM budget here is per-CHUNK, not per-table, so any
sequence length fits once ``(page_chunk, head_block)`` does.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.acdc_cascade_fused import VMEM_BUDGET

#: page-chunk candidates (pages DMA'd per streaming round), largest first
PAGE_CHUNKS = (8, 4, 2, 1)
#: KV-head row-block candidates, largest first (a call site uses only
#: those :func:`legal_head_block` accepts for its Hkv)
HEAD_BLOCKS = (8, 4, 2, 1)
#: deterministic off-device answer, pre-clamp
DEFAULT_BLOCK = (4, 4)

#: force the fused kernel even off-TPU (interpret mode) — parity tests
#: and benches flip this; default routing sends CPU runs to the gather
#: fallback (interpret-mode DMA walks are correctness-only).
FORCE_FUSED = os.environ.get("REPRO_PAGED_ATTN", "").lower() in (
    "fused", "force", "1")


def encode_block(blk: Tuple[int, int]) -> int:
    """Pack (page_chunk, head_block) into the autotune cache's int slot."""
    pc, bh = blk
    return pc * 256 + bh


def decode_block(enc: int) -> Tuple[int, int]:
    return enc // 256, enc % 256


def paged_attn_vmem_bytes(*, bs: int, dh: int, group: int, t: int,
                          pc: int, bh: int, itemsize: int) -> int:
    """Per-program VMEM footprint: chunk buffers + fp32 softmax state.

    Per-CHUNK, not per-table: the streamed K/V lives in a
    ``(pc, bs, bh, dh)`` double slot reused every round, so table
    capacity MB never enters the budget.
    """
    stream = 2 * pc * bs * bh * dh * itemsize          # k + v chunk bufs
    q = t * bh * group * dh * 4                        # fp32 query tile
    state = bh * group * t * (dh + 2) * 4              # acc + m + l, fp32
    newkv = 2 * t * bh * dh * itemsize                 # new-token K/V
    out = t * bh * group * dh * itemsize
    return stream + q + state + newkv + out


def legal_head_block(bh: int, hkv: int) -> bool:
    """Whether the TPU compiler accepts ``bh`` heads per program: the
    new-token K/V blocks ``(1, T, bh, Dh)`` put ``bh`` on the sublane
    axis, which Mosaic tiles by 8 unless the block spans all ``hkv``."""
    return hkv % bh == 0 and (bh == hkv or bh % 8 == 0)


def pick_block(*, hkv: int, dh: int, group: int, t: int, bs: int,
               itemsize: int) -> Optional[Tuple[int, int]]:
    """Largest in-budget (page_chunk, head_block), or None if nothing
    fits (the dispatcher then keeps the gather fallback)."""
    for pc in PAGE_CHUNKS:
        for bh in HEAD_BLOCKS:
            if not legal_head_block(bh, hkv):
                continue
            if paged_attn_vmem_bytes(bs=bs, dh=dh, group=group, t=t,
                                     pc=pc, bh=bh,
                                     itemsize=itemsize) <= VMEM_BUDGET:
                return pc, bh
    return None


def clamp_block(blk: Tuple[int, int], *, hkv: int, dh: int, group: int,
                t: int, bs: int, itemsize: int) -> Optional[Tuple[int, int]]:
    """Fit an autotuned/default (pc, bh) to this call site: bh must be a
    :func:`legal_head_block` for Hkv and the pair must be in budget;
    degrade toward :func:`pick_block`'s answer rather than fail."""
    pc, bh = blk
    legal = [h for h in range(min(bh, hkv), 0, -1)
             if legal_head_block(h, hkv)]
    if legal and paged_attn_vmem_bytes(
            bs=bs, dh=dh, group=group, t=t, pc=pc, bh=legal[0],
            itemsize=itemsize) <= VMEM_BUDGET:
        return pc, legal[0]
    return pick_block(hkv=hkv, dh=dh, group=group, t=t, bs=bs,
                      itemsize=itemsize)


def _kernel(virtual, t, bs, pc, bh, group, dh, softcap,
            routed_r, pos_r, start_r, nch_r, phys_r, off_r, win_r, layer_r,
            q_ref, kn_ref, vn_ref, kp_hbm, vp_hbm,
            o_ref, kp_out, vp_out, kbuf, vbuf, sem_k, sem_v, sem_s):
    # Mosaic lowers 2-D matmuls, reductions and masks; every per-head
    # tile below is therefore 2-D: query rows ``r = tt * group + g``
    # (tg of them) against one key per column.
    i = pl.program_id(0)
    h0 = pl.program_id(1) * bh
    tg = t * group
    lay = layer_r[0]

    # -- 1. persist the T new tokens' K/V head-slice into their (already
    #    trash-routed) tail pages of layer ``lay``.  Disjoint from every
    #    streamed read (reads stop at kpos < position), so no ordering
    #    hazard.
    for tt in range(t):
        page = phys_r[i, tt]
        o = off_r[i, tt]
        ck = pltpu.make_async_copy(
            kn_ref.at[0, tt], kp_out.at[lay, page, o, pl.ds(h0, bh)],
            sem_s.at[0])
        cv = pltpu.make_async_copy(
            vn_ref.at[0, tt], vp_out.at[lay, page, o, pl.ds(h0, bh)],
            sem_s.at[1])
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()

    # -- 2. online softmax over the streamed prefix + the new tokens.
    scale = dh ** -0.5
    pos_i = pos_r[i]
    win = win_r[0]
    # the window as a distance bound; "global" is one no distance reaches
    wlim = jnp.where(win > 0, win, jnp.int32(2 ** 30))
    qs = [q_ref[0, hh].astype(jnp.float32) for hh in range(bh)]  # (tg, dh)

    def query_pos(shape):
        """(tg, kk) position of each query row: pos + row // group."""
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        qp = jnp.full(shape, pos_i, jnp.int32)
        for tt in range(1, t):
            qp = qp + jnp.where(row >= tt * group, 1, 0)
        return qp

    def fold(state, q, kc, vc, msk):
        """One block of keys into a head's running (m, l, acc) state.
        q: (tg, dh) fp32; kc/vc: (kk, dh); msk: (tg, kk), True = attend."""
        m, l, acc = state
        s = jax.lax.dot_general(
            q, kc.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(msk, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.dot(p, vc.astype(jnp.float32),
                                       preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def chunk(ci, carry):
        base = ci * pc
        for jj in range(pc):                           # static unroll
            page = routed_r[i, base + jj]
            pltpu.make_async_copy(kp_hbm.at[lay, page, :, pl.ds(h0, bh)],
                                  kbuf.at[jj], sem_k.at[jj]).start()
            pltpu.make_async_copy(vp_hbm.at[lay, page, :, pl.ds(h0, bh)],
                                  vbuf.at[jj], sem_v.at[jj]).start()
        for jj in range(pc):
            page = routed_r[i, base + jj]
            pltpu.make_async_copy(kp_hbm.at[lay, page, :, pl.ds(h0, bh)],
                                  kbuf.at[jj], sem_k.at[jj]).wait()
            pltpu.make_async_copy(vp_hbm.at[lay, page, :, pl.ds(h0, bh)],
                                  vbuf.at[jj], sem_v.at[jj]).wait()
        shape = (tg, pc * bs)
        kpos = base * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # streamed keys are the prefix below the row's first write
        msk = jnp.logical_and(kpos < pos_i, query_pos(shape) - kpos < wlim)
        return tuple(
            fold(carry[hh], qs[hh],
                 kbuf[:, :, hh, :].reshape(pc * bs, dh),
                 vbuf[:, :, hh, :].reshape(pc * bs, dh), msk)
            for hh in range(bh))

    init = tuple((jnp.full((tg, 1), -jnp.inf, jnp.float32),
                  jnp.zeros((tg, 1), jnp.float32),
                  jnp.zeros((tg, dh), jnp.float32)) for _ in range(bh))
    start_i = start_r[i]
    carry = jax.lax.fori_loop(start_i, start_i + nch_r[i], chunk, init)

    # new tokens attend each other straight from VMEM (same values the
    # scatter just wrote), under the exact gather-path mask
    shape = (tg, t)
    knpos = pos_i + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    qp = query_pos(shape)
    msk = jnp.logical_and(jnp.logical_and(knpos <= qp, knpos < virtual),
                          qp - knpos < wlim)
    for hh in range(bh):
        _, l, acc = fold(carry[hh], qs[hh], kn_ref[0, :, hh, :],
                         vn_ref[0, :, hh, :], msk)
        o_ref[0, hh] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,                   # (B, T, Hq, Dh) post-RoPE queries
    knew: jax.Array,                # (B, T, Hkv, Dh) post-RoPE new keys
    vnew: jax.Array,                # (B, T, Hkv, Dh) new values
    k_pages: jax.Array,             # ([L,] NB+1, bs, Hkv, Dh) page pool
    v_pages: jax.Array,
    block_tables: jax.Array,        # (B, MB) int32, -1 = unmapped
    position: jax.Array,            # (B,) first write index per row
    window: jax.Array,              # traced int32 scalar, 0 = global
    layer: Optional[jax.Array] = None,  # traced int32 scalar
    *,
    softcap: float,
    page_chunk: int,
    head_block: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused decode/verify attention against the paged pool.

    Returns ``(out (B, T, Hq, Dh), k_pages, v_pages)`` with the T new
    tokens' K/V scattered into the (aliased, in-place) pools — drop-in
    for the scatter+gather+SDPA sequence in ``models/attention.py``.

    With ``layer`` the pools are the whole stacked ``(L, NB+1, bs, Hkv,
    Dh)`` cache and the kernel reads and writes only that layer's pages
    of it, so a layer loop can carry the pools without slicing one out;
    without it they are one layer's ``(NB+1, bs, Hkv, Dh)``.
    """
    if layer is None:
        out, kp, vp = paged_attention(
            q, knew, vnew, k_pages[None], v_pages[None], block_tables,
            position, window, jnp.int32(0), softcap=softcap,
            page_chunk=page_chunk, head_block=head_block,
            interpret=interpret)
        return out, kp[0], vp[0]
    b, t, hq, dh = q.shape
    hkv = knew.shape[2]
    group = hq // hkv
    n_pages, bs = k_pages.shape[1], k_pages.shape[2]
    mb = block_tables.shape[1]
    virtual = mb * bs
    pc, bh = page_chunk, head_block
    if hkv % bh:
        raise ValueError(f"head_block {bh} must divide n_kv_heads {hkv}")

    # scalar-prefetch operands (SMEM): the routed table, per-row chunk
    # range, and the pre-routed scatter targets
    routed = jnp.where(block_tables >= 0, block_tables, 0).astype(jnp.int32)
    mbp = -(-mb // pc) * pc
    if mbp > mb:
        routed = jnp.pad(routed, ((0, 0), (0, mbp - mb)))
    pos = position.astype(jnp.int32)
    qpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]   # (B,T)
    blk_idx = jnp.minimum(qpos // bs, mb - 1)
    phys = jnp.take_along_axis(block_tables, blk_idx, axis=1)
    writable = jnp.logical_and(phys >= 0, qpos < virtual)
    phys = jnp.where(writable, phys, n_pages - 1).astype(jnp.int32)
    off = (qpos % bs).astype(jnp.int32)
    win = jnp.reshape(window, (1,)).astype(jnp.int32)
    lay = jnp.reshape(layer, (1,)).astype(jnp.int32)
    span = bs * pc
    frontier = jnp.minimum(pos, virtual)
    start = jnp.where(win[0] > 0,
                      jnp.maximum(pos - win[0] + 1, 0) // span,
                      0).astype(jnp.int32)
    nch = jnp.maximum((frontier + span - 1) // span - start, 0)
    # parked rows (pos >= virtual) stream nothing — their (discarded)
    # output costs zero pool bytes; this is where the O(len) claim comes
    # from for an idle slot
    nch = jnp.where(pos >= virtual, 0, nch).astype(jnp.int32)

    # head-major query rows: (B, Hkv, T * group, Dh), row = tt * group + g
    tg = t * group
    qh = q.reshape(b, t, hkv, group, dh).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, tg, dh)
    kernel = functools.partial(_kernel, virtual, t, bs, pc, bh, group, dh,
                               float(softcap))
    # blocks keep their leading unit dim (a squeezed one would make the
    # Mosaic verifier reject the tail-page DMA's slice of the block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(b, hkv // bh),
        in_specs=[
            pl.BlockSpec((1, bh, tg, dh), lambda i, j, *_: (i, j, 0, 0)),
            pl.BlockSpec((1, t, bh, dh), lambda i, j, *_: (i, 0, j, 0)),
            pl.BlockSpec((1, t, bh, dh), lambda i, j, *_: (i, 0, j, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, tg, dh), lambda i, j, *_: (i, j, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((pc, bs, bh, dh), k_pages.dtype),
            pltpu.VMEM((pc, bs, bh, dh), v_pages.dtype),
            pltpu.SemaphoreType.DMA((pc,)),
            pltpu.SemaphoreType.DMA((pc,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out, kp, vp = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, tg, dh), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operand indices count the 8 scalar-prefetch args: the pools are
        # operands 11/12 and alias outputs 1/2 (in-place update)
        input_output_aliases={11: 1, 12: 2},
        interpret=interpret,
    )(routed, pos, start, nch, phys, off, win, lay,
      qh, knew.astype(k_pages.dtype), vnew.astype(v_pages.dtype),
      k_pages, v_pages)
    out = out.reshape(b, hkv, t, group, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, hq, dh), kp, vp
