"""Pallas TPU kernels for the perf-critical ACDC hot path.

Layout (per repo convention):

* ``acdc_fused.py``         — single-call fused forward (8N bytes/row);
  also home of ``MAX_FUSED_N``, the VMEM gate shared by every fused path.
* ``acdc_bwd.py``           — fused per-layer backward (paper eqs. 10-14)
  in one kernel per row-block: recomputes ``h2`` in VMEM (section 5.3
  trade), emits the dx tile, accumulates da/dd/dbias in fp32 VMEM scratch
  across the row grid.  Two-call degradation for N > ``MAX_FUSED_N``.
* ``acdc_cascade_fused.py`` — order-K cascade forward in ONE kernel: the
  activation row-block stays in VMEM across all K layers (8N bytes/row
  independent of K, vs 8KN for the per-layer scan), with interleaved ReLU
  fused on the VPU and the riffle permutation folded into the columns of
  the mid-cascade C^T (no in-kernel gathers).  ``fits_vmem`` documents
  and enforces the budget: (2-3) N^2 transform matrices + K stacked
  diagonals + row tiles.
* ``acdc_cascade_bwd.py``   — order-K REVERSE-SWEEP backward in ONE
  kernel: forward re-walk of the x tile stashes the K-1 layer inputs in
  VMEM scratch, then the eqs. 10-14 sweep runs layer K-1..0 with the
  cotangent block resident — 12N HBM bytes/row independent of K.  Its
  VMEM budget includes the (K-1, bm, N) stash, so the row block shrinks
  with depth and ``ops.py`` falls back to the per-layer scan when no
  block fits.
* ``paged_attn.py``         — fused paged-attention decode/verify kernel
  for the serving engine: walks each slot's block table from SMEM,
  DMA-streams only the mapped in-frontier K/V pages chunk-by-chunk
  through double-width VMEM scratch, runs online-softmax per chunk with
  the causal/window mask derived from ``position``, and scatters the new
  token's K/V into the tail page in the same program (pool aliased
  in-place).  One body serves both grids: decode (T=1) and speculative
  verify (T=k+1).  The ``(B, virtual, Hkv, Dh)`` gather view is never
  materialised.
* ``acdc_factored.py``      — per-layer forward above ``MAX_FUSED_N`` for
  the DCT family (N a multiple of 128): the DCT factored four-step style
  into N1 x N1 and 128 x 128 complex stages around a twiddle diagonal,
  all operands resident in VMEM, one call per row block — no N x N
  matrix is streamed.  The served path of every large ACDC projection.
* ``scaled_matmul.py``      — blocked (m,n,k) scaled matmul kernel; the
  building block of the other > ``MAX_FUSED_N`` regimes: the per-layer
  backward, and the forward of the other families and of N not a
  multiple of 128.
* ``autotune.py``           — first-call on-device row-block sweep
  ({64, 128, 256}, memoized per (N, K, dtype, direction) and persisted
  to ``.cache/autotune.json`` for device runs) feeding ``bm`` to
  the fused fwd/bwd/cascade/cascade_bwd kernels; returns the old fixed
  constants off-device so CPU/CI runs are unchanged.
* ``ops.py``                — jit'd public wrappers + custom VJPs:
  per-layer ``acdc_fused``/``acdc_fused_nobias`` (fused Pallas backward)
  and cascade-level ``acdc_cascade_op`` (whole-cascade forward fusion,
  reverse-sweep backward, per-layer-scan fallback; routing counted in
  ``CASCADE_BWD_DISPATCHES``).
* ``ref.py``                — pure-jnp oracles the tests assert against,
  including the four-matmul backward formulation the fused kernel
  replaced.

Backward memory model, per row of an order-K cascade (the trajectory
BENCH_kernels.json tracks; N fp32 features, transform matrices excluded
as batch-amortized)::

    four XLA matmuls / layer     48N * K   gc, h2, dh1 each round-trip HBM
    fused per-layer kernel       12N * K   x, g in, dx out — per layer,
      (+ scan remat)           + 8N*(K-1)  layer inputs written+read back
    reverse-sweep kernel         12N       x, g in, dx out ONCE; stash
                                           and cotangent live in VMEM,
                                           independent of K

The forward trajectory is the analogous 48N -> 8N*K -> 8N (whole-cascade
fusion).  Together they put the full training step, not just inference,
at the paper's section 5 roofline.

Serving-side attention memory model, per slot per layer per tick (the
trajectory BENCH_serve.json tracks; MB = pages per slot row, B = tokens
per page, len = the slot's live length)::

    block-table gather     MB * B * Hkv * Dh * 2 * itemsize   the whole
                           virtual row, K and V, regardless of fill
    fused streaming        ceil(len / B) * B * Hkv * Dh * 2 * itemsize
                           only mapped in-frontier pages; parked and
                           stalled rows cost zero

i.e. gather traffic is O(max_len) per slot while the kernel's is O(len)
— independent of how generously the page table is provisioned.  Routing
lives in ``ops.paged_attn_route`` (counted in ``PAGED_ATTN_DISPATCHES``):
fused on TPU (or when forced via ``REPRO_PAGED_ATTN=fused``) when an
autotuned ``(page_chunk, head_block)`` fits the per-chunk VMEM budget,
gather otherwise.

Transform-family support matrix (``core/families.py``): the kernel
bodies take ``C``/``C^T`` (and the riffle-folded ``C^T[:, perm]``) as
operands, so every real-orthonormal family runs the SAME kernels — the
family only changes which matrices ``ops.py`` feeds them and which key
the autotuner sweeps under::

    family      fused fwd   fused bwd   cascade fwd   cascade bwd   N > 1024 fwd
    acdc        yes         yes         yes           yes           factored (N % 128 == 0)
    circulant   yes         yes         yes           yes           two-call
    hadamard    yes         yes         yes           yes           two-call

(DCT-II, real-DFT and pow2-N Walsh-Hadamard respectively.)  Above
``MAX_FUSED_N`` every family's backward is the two-call
``acdc_bwd_two_call``; only the DCT's forward has a factored kernel —
the others would need factorizations of their own (Hadamard is a plain
Kronecker product).  ``ops.ACDC_FWD_DISPATCHES`` counts the route each
traced forward took.

``autotune.py`` keys its memo/persistent cache on
``(direction, n, k, dtype, bias, permute, family)`` so a block size
swept for one family's matrix pair is never reused for another's
(pre-family 6-field cache entries are migrated on load as ``acdc``).
A family with ``complex_diagonals=True`` would NOT get the fused paths
(the kernels are real-only); all registered families are real.
"""
