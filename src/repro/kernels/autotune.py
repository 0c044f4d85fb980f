"""First-call on-device block-size autotuning for the fused ACDC kernels.

The fused kernels used fixed row blocks (``bm`` = 256 forward / 128
backward, budget-derived for the cascade).  The VMEM-occupancy sweet spot
shifts with N, cascade depth, dtype and TPU generation, so on the first
call for a given ``(N, K, dtype, direction)`` this module times a tiny
on-device sweep over the candidate blocks {64, 128, 256} and memoizes the
winner for the process lifetime.  Off-device (CPU tests / CI, where the
kernels run in interpret mode and timings are meaningless) the sweep is
skipped and the previous fixed constants come back unchanged, so tuned
and untuned runs share one code path.

The call sites (``ops.py``'s custom-VJP impls) are almost always first
hit INSIDE a ``jit`` trace, where omnistaging would stage the sweep's
work as tracers instead of running it.  The sweep therefore escapes the
trace explicitly: sample operands are built concrete under
``jax.ensure_compile_time_eval()`` and each candidate kernel is
dispatched through an AOT ``lower(...).compile()`` executable (compiled
callables run for real whatever the ambient trace state), so the timing
happens on device at trace time and only the chosen ``bm`` (a static
Python int) shapes the traced kernel.

Directions: ``fwd``/``bwd`` (per-layer kernels), ``cascade`` (fused
forward), ``cascade_bwd`` (reverse-sweep backward; candidates filtered
by its stash-inclusive VMEM budget), and ``paged_attn`` (the serving
decode/verify kernel: candidates are (page_chunk, head_block) pairs
packed into the cache's int slot via ``paged_attn.encode_block``,
filtered by the kernel's per-chunk budget, keyed on (head_dim, T)).

Sweep winners also persist across processes: real device sweeps are
spilled to ``.cache/autotune.json`` (``repro.cache``; keyed by backend —
fallback constants never leak between backends) and reloaded lazily on
the first TPU-side miss, so repeated ``launch/train`` runs skip the
first-call on-device sweep.  ``REPRO_AUTOTUNE_CACHE=0`` disables the
file; ``REPRO_AUTOTUNE_CACHE_PATH`` relocates it.

Keys carry the transform FAMILY (``core/families.py``): the sweep's
operands are the family's own ``C``/``C^T`` matrices, and a winner swept
for one family is never served to another (different matrix constant ->
different VMEM/MXU behavior is possible even at equal shapes).  Entries
persisted before the family field existed (6-field keys) are migrated on
load by tagging them ``acdc`` — every pre-family sweep ran the DCT — so
e.g. a ``circulant`` run can never reuse a DCT-swept block size.
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import cache as cache_mod
from repro.core import families as families_mod
from repro.kernels import acdc_bwd as bwd_mod
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.kernels import acdc_cascade_bwd as cascade_bwd_mod
from repro.kernels import acdc_cascade_fused as cascade_mod
from repro.kernels import acdc_fused as fused_mod
from repro.kernels import paged_attn as paged_attn_mod

#: candidate row blocks, smallest first (the sweep skips ones over budget)
CANDIDATE_BMS = (64, 128, 256)
#: rows in the sweep's sample batch — enough grid steps to see pipelining
SWEEP_ROWS = 1024
#: timing repetitions per candidate (after one compile/warmup call)
SWEEP_REPS = 3

#: set to "0"/"off"/"false" to disable the on-disk sweep-result cache
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

#: representative serving dims for the ``paged_attn`` sweep — the cache
#: key only carries (head_dim, T), so the sweep fixes the rest at the
#: engine defaults; winners are clamped to the real call site's head
#: count by ``paged_attn.clamp_block``
_PAGED_SWEEP = {"hkv": 8, "group": 4, "bs": 16, "mb": 16, "rows": 8,
                "pool": 128}

_CACHE: Dict[Tuple, int] = {}
_PERSIST_LOADED = False

#: real on-device sweeps completed this process, labeled by direction —
#: fallbacks and memo/persist hits do NOT count (a run that shows zero
#: sweeps either hit the disk cache or never touched a TPU)
_SWEEPS = obs_metrics.REGISTRY.counter(
    "autotune_sweeps_total", "on-device block-size sweeps completed",
    labels=("direction",))


def _fallback(direction: str, n: int, k: int, *, bias: bool,
              permute: bool) -> int:
    """The pre-autotune fixed constants (also the no-device answer)."""
    if direction == "fwd":
        return fused_mod.DEFAULT_BM
    if direction == "bwd":
        return bwd_mod.DEFAULT_BM
    if direction == "cascade":
        bm = cascade_mod.pick_bm(n, k, permute=permute, bias=bias)
        return bm if bm is not None else cascade_mod.DEFAULT_BM
    if direction == "cascade_bwd":
        bm = cascade_bwd_mod.pick_bm(n, k, permute=permute, bias=bias)
        return bm if bm is not None else cascade_bwd_mod.DEFAULT_BM
    if direction == "paged_attn":
        # key reuse: n = head_dim, k = T (decode 1 / verify k+1); the
        # sweep's other dims are representative (clamped per call site)
        blk = paged_attn_mod.pick_block(
            hkv=_PAGED_SWEEP["hkv"], dh=n, group=_PAGED_SWEEP["group"],
            t=k, bs=_PAGED_SWEEP["bs"], itemsize=4)
        return paged_attn_mod.encode_block(
            blk if blk is not None else paged_attn_mod.DEFAULT_BLOCK)
    raise ValueError(f"unknown direction {direction!r}")


def _candidates(direction: str, n: int, k: int, *, bias: bool,
                permute: bool):
    if direction == "cascade":
        return [bm for bm in CANDIDATE_BMS
                if cascade_mod.cascade_vmem_bytes(
                    n, k, permute=permute, bias=bias,
                    bm=bm) <= cascade_mod.VMEM_BUDGET]
    if direction == "cascade_bwd":
        return [bm for bm in CANDIDATE_BMS
                if cascade_bwd_mod.cascade_bwd_vmem_bytes(
                    n, k, permute=permute, bias=bias,
                    bm=bm) <= cascade_mod.VMEM_BUDGET]
    if direction == "paged_attn":
        # page-chunk x head-block grid, encoded into the cache's int
        # slot; budget is the kernel's per-chunk VMEM model
        return [paged_attn_mod.encode_block((pc, bh))
                for pc in paged_attn_mod.PAGE_CHUNKS
                for bh in paged_attn_mod.HEAD_BLOCKS
                if paged_attn_mod.legal_head_block(bh, _PAGED_SWEEP["hkv"])
                and paged_attn_mod.paged_attn_vmem_bytes(
                    bs=_PAGED_SWEEP["bs"], dh=n,
                    group=_PAGED_SWEEP["group"], t=k, pc=pc, bh=bh,
                    itemsize=4) <= cascade_mod.VMEM_BUDGET]
    return list(CANDIDATE_BMS)


# ---------------------------------------------------------------------------
# Persistent sweep cache (.cache/autotune.json, beside the compile cache).
#
# Sweeps are memoized per process; a fresh ``launch/train`` run used to
# re-pay the first-call on-device sweep for every (N, K, dtype,
# direction).  Swept winners are spilled to a small JSON and reloaded on
# startup.  Only REAL device sweeps are persisted (the file records the
# backend and is ignored under any other), so CPU fallback constants
# never leak into a TPU run.  Set REPRO_AUTOTUNE_CACHE=0 to disable.
# ---------------------------------------------------------------------------

def _backend() -> str:
    return jax.default_backend()


def _persist_enabled() -> bool:
    return os.environ.get(CACHE_ENV, "1").lower() not in (
        "0", "off", "false", "no")


def _cache_path() -> str:
    override = os.environ.get(CACHE_ENV + "_PATH")
    if override:
        return override
    return str(cache_mod.AUTOTUNE_CACHE_PATH)


def _key_str(key: Tuple) -> str:
    return "|".join(str(p) for p in key)


def _key_from_str(s: str) -> Tuple:
    parts = s.split("|")
    if len(parts) == 6:
        # pre-family entry: every sweep recorded before the transform
        # registry existed ran the DCT, so migrate rather than discard —
        # but NEVER let another family inherit it.
        parts.append("acdc")
    direction, n, k, dtype, bias, permute, family = parts
    return (direction, int(n), int(k), dtype,
            bias == "True", permute == "True", family)


def _load_persistent() -> None:
    """Merge on-disk sweep winners into the in-process memo (lazy, once)."""
    global _PERSIST_LOADED
    if _PERSIST_LOADED:
        return
    _PERSIST_LOADED = True
    if not _persist_enabled():
        return
    try:
        with open(_cache_path()) as f:
            blob = json.load(f)
    except (OSError, ValueError):
        return
    if blob.get("backend") != _backend():
        return
    for key_s, bm in blob.get("entries", {}).items():
        try:
            _CACHE.setdefault(_key_from_str(key_s), int(bm))
        except (ValueError, TypeError):
            continue


def _save_persistent(key: Tuple, bm: int) -> None:
    """Record one swept winner on disk (read-merge-write, best effort)."""
    if not _persist_enabled():
        return
    path = _cache_path()
    entries: Dict[str, int] = {}
    try:
        with open(path) as f:
            blob = json.load(f)
        if blob.get("backend") == _backend():
            entries = dict(blob.get("entries", {}))
    except (OSError, ValueError):
        pass
    entries[_key_str(key)] = int(bm)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"backend": _backend(), "entries": entries}, f,
                      indent=2, sort_keys=True)
    except OSError:
        pass


def _make_runner(direction: str, n: int, k: int, dtype, *, bias: bool,
                 permute: bool, family: str = "acdc",
                 interpret: bool) -> Callable[[int], Callable[[], None]]:
    """Build ``build(bm) -> run()``: an AOT-compiled single kernel call on
    sample operands.  Compilation happens in ``build`` (outside the timed
    region); ``run`` only dispatches and blocks.  Operands are created
    under ``ensure_compile_time_eval`` and the call goes through
    ``lower(...).compile()`` so both stay concrete when the sweep is
    first hit inside an enclosing ``jit`` trace."""
    if direction == "paged_attn":
        return _make_paged_runner(n, k, dtype, interpret=interpret)
    fam = families_mod.get_family(family)
    with jax.ensure_compile_time_eval():
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (SWEEP_ROWS, n), dtype)
        c, ct = fam.matrices(n, jnp.float32)
        if direction in ("cascade", "cascade_bwd"):
            a = jnp.ones((k, n), jnp.float32)
            d = jnp.ones((k, n), jnp.float32)
            b = jnp.zeros((k, n), jnp.float32) if bias else None
            ct_mid = (ct[:, fam.riffle(n)] if permute else None)
        else:
            a = jnp.ones((n,), jnp.float32)
            d = jnp.ones((n,), jnp.float32)
            b = jnp.zeros((n,), jnp.float32) if bias else None
        if direction in ("bwd", "cascade_bwd"):
            g = jax.random.normal(jax.random.fold_in(key, 1),
                                  (SWEEP_ROWS, n), dtype)

    def build(bm: int) -> Callable[[], None]:
        if direction == "cascade":
            args = (x, a, d, b, c, ct, ct_mid)
            compiled = cascade_mod.acdc_cascade_pallas.lower(
                *args, relu=False, bm=bm, interpret=interpret).compile()
        elif direction == "cascade_bwd":
            args = (x, g, a, d, b, c, ct, ct_mid)
            compiled = cascade_bwd_mod.acdc_cascade_bwd_pallas.lower(
                *args, relu=False, bm=bm, interpret=interpret).compile()
        elif direction == "fwd":
            args = (x, a, d, b, c, ct)
            compiled = fused_mod.acdc_fused_pallas.lower(
                *args, bm=bm, interpret=interpret).compile()
        else:
            args = (x, g, a, d, c, ct)
            compiled = bwd_mod.acdc_bwd_pallas.lower(
                *args, with_bias=bias, bm=bm, interpret=interpret).compile()

        def run() -> None:
            jax.block_until_ready(compiled(*args))

        run.bm = bm
        return run

    return build


def _make_paged_runner(dh: int, t: int, dtype, *,
                       interpret: bool) -> Callable[[int], Callable[[], None]]:
    """``build(encoded_block) -> run()`` for the paged-attention sweep:
    one fused decode/verify dispatch on representative serving operands
    (``_PAGED_SWEEP`` dims, rows mid-stream so pages actually stream)."""
    dims = _PAGED_SWEEP
    hkv, group, bs = dims["hkv"], dims["group"], dims["bs"]
    rows, mb, pool = dims["rows"], dims["mb"], dims["pool"]
    hq = hkv * group
    with jax.ensure_compile_time_eval():
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (rows, t, hq, dh), dtype)
        kn = jax.random.normal(jax.random.fold_in(key, 1),
                               (rows, t, hkv, dh), dtype)
        vn = jax.random.normal(jax.random.fold_in(key, 2),
                               (rows, t, hkv, dh), dtype)
        kp = jnp.zeros((pool + 1, bs, hkv, dh), dtype)
        vp = jnp.zeros((pool + 1, bs, hkv, dh), dtype)
        tbl = jnp.arange(rows * mb, dtype=jnp.int32).reshape(rows, mb) % pool
        pos = jnp.full((rows,), (mb * bs) // 2, jnp.int32)
        win = jnp.int32(0)
        args = (q, kn, vn, kp, vp, tbl, pos, win)

    def build(enc: int) -> Callable[[], None]:
        pc, bh = paged_attn_mod.decode_block(enc)
        fn = jax.jit(functools.partial(
            paged_attn_mod.paged_attention, softcap=0.0, page_chunk=pc,
            head_block=bh, interpret=interpret))
        compiled = fn.lower(*args).compile()

        def run() -> None:
            jax.block_until_ready(compiled(*args))

        run.bm = enc
        return run

    return build


def sweep(direction: str, n: int, k: int = 1, dtype=jnp.float32, *,
          bias: bool = False, permute: bool = False,
          family: str = "acdc", interpret: bool = False,
          timer: Optional[Callable[[Callable[[], None]], float]] = None) -> int:
    """Time every in-budget candidate and return the fastest ``bm``.

    ``timer`` (seconds for one call of a nullary thunk) is injectable for
    tests; the default runs one warmup/compile call then best-of-
    ``SWEEP_REPS`` wall clock.
    """
    cands = _candidates(direction, n, k, bias=bias, permute=permute)
    if not cands:
        return _fallback(direction, n, k, bias=bias, permute=permute)
    build = _make_runner(direction, n, k, dtype, bias=bias, permute=permute,
                         family=family, interpret=interpret)

    def default_timer(thunk: Callable[[], None]) -> float:
        thunk()  # warmup outside the timed reps (compile already done)
        best = float("inf")
        for _ in range(SWEEP_REPS):
            t0 = time.perf_counter()
            thunk()
            best = min(best, time.perf_counter() - t0)
        return best

    timer = timer or default_timer
    timings = [(timer(build(bm)), bm) for bm in cands]
    return min(timings)[1]


def autotuned_bm(direction: str, n: int, k: int = 1, dtype=jnp.float32, *,
                 bias: bool = False, permute: bool = False,
                 family: str = "acdc") -> int:
    """Memoized block size for ``(N, K, dtype, direction, family)`` (+ the
    budget knobs bias/permute): on-device sweep on TPU, fixed fallback
    elsewhere.  ``family`` keys the memo AND shapes the sweep operands —
    a winner timed on one family's matrices never answers for another's.
    """
    key = (direction, int(n), int(k), jnp.dtype(dtype).name, bool(bias),
           bool(permute), family)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    if _backend() != "tpu":
        bm = _fallback(direction, n, k, bias=bias, permute=permute)
    else:
        _load_persistent()
        hit = _CACHE.get(key)
        if hit is not None:
            return hit
        # a candidate the compiler refuses is a bug in the kernel or its
        # budget model: it raises here rather than hide behind a fallback
        bm = sweep(direction, n, k, dtype, bias=bias, permute=permute,
                   family=family)
        _save_persistent(key, bm)
        _SWEEPS.labels(direction=direction).inc()
        obs_trace.instant_global("autotune", "sweep", direction=direction,
                                 key=_key_str(key), winner=int(bm))
    _CACHE[key] = bm
    return bm
