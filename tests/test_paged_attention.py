"""Fused paged-attention decode/verify kernel: parity and routing.

* direct kernel-vs-gather parity on synthetic operands: decode (T=1) and
  verify (T=k+1) grids, ragged positions straddling page boundaries,
  sliding window + logit softcap, page-table padding (max_blocks not a
  multiple of the page chunk), parked rows, in-contract unmapped tables
  (admission-tick shapes), and bfloat16 pools with BITWISE scatter parity;
  on a stacked (L, NB+1, ...) pool addressed by layer, the same output and
  rows as the single-layer call on that layer, other layers untouched;
* dispatch discipline: CPU default routes to the gather fallback, forcing
  the kernel routes fused, an over-budget block (no (page_chunk,
  head_block) fits VMEM) falls back to gather — every decision recorded in
  ``ops.PAGED_ATTN_DISPATCHES``;
* engine-level greedy stream identity, fused vs gather, for every pageable
  family in plain decode AND speculative verify;
* page-recycling regression: pages freed by eviction and LIFO-remapped to
  a *different* slot mid-stream must not leak stale K/V through the causal
  mask (dense parity across evict->admit cycles on a tight pool).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import ops
from repro.kernels import paged_attn
from repro.kernels.ref import paged_attention_ref
from repro.models import get_model
from repro.serving import Engine, Request
from repro.spec import ModelDraft


# ---------------------------------------------------------------------------
# Direct kernel parity vs the block-table gather (no engine).
# ---------------------------------------------------------------------------

def _seq_tables(b, mb, nb):
    t = np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    assert t.max() < nb
    return jnp.asarray(t)


def _unmapped_tables(b, mb, nb):
    # admission-tick shape: row 0 mapped only below its frontier, row 1
    # fully unmapped but PARKED (pos == virtual) — the only unmapped states
    # the allocator ever hands the kernel
    t = np.full((b, mb), -1, np.int32)
    t[0, :2] = [3, 4]
    return jnp.asarray(t)


_CASES = {
    "decode-global": dict(b=3, t=1, hkv=4, group=2, dh=8, bs=4, mb=6, nb=32,
                          window=0, softcap=0.0, pc=2, bh=2,
                          positions=[5, 0, 17], tables=_seq_tables),
    "verify-ragged-parked": dict(b=4, t=4, hkv=4, group=2, dh=16, bs=4,
                                 mb=6, nb=32, window=0, softcap=0.0, pc=2,
                                 bh=4, positions=[2, 7, 22, 24],
                                 tables=_seq_tables),
    "verify-window-pad": dict(b=2, t=3, hkv=4, group=1, dh=8, bs=4, mb=5,
                              nb=16, window=6, softcap=50.0, pc=2, bh=2,
                              positions=[9, 14], tables=_seq_tables),
    "decode-unmapped": dict(b=2, t=1, hkv=2, group=2, dh=8, bs=4, mb=4,
                            nb=16, window=0, softcap=0.0, pc=2, bh=2,
                            positions=[6, 16], tables=_unmapped_tables),
    "decode-bf16": dict(b=3, t=2, hkv=4, group=2, dh=8, bs=4, mb=6, nb=32,
                        window=0, softcap=0.0, pc=2, bh=2,
                        positions=[5, 0, 17], tables=_seq_tables,
                        dtype=jnp.bfloat16),
}
# the stacked (L, NB+1, ...) pool a layer loop carries, addressed by layer
for _layer in (0, 2):
    _CASES[f"decode-stacked-l{_layer}"] = dict(
        _CASES["decode-global"], layers=3, layer=_layer)
    _CASES[f"verify-stacked-l{_layer}"] = dict(
        _CASES["verify-ragged-parked"], t=3, layers=3, layer=_layer)


@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_matches_gather(case):
    c = dict(_CASES[case])
    dtype = c.pop("dtype", jnp.float32)
    tables, positions, pc, bh = (c.pop("tables"), c.pop("positions"),
                                 c.pop("pc"), c.pop("bh"))
    layers, layer = c.pop("layers", None), c.pop("layer", None)
    b, t, hkv, group, dh = c["b"], c["t"], c["hkv"], c["group"], c["dh"]
    bs, mb, nb = c["bs"], c["mb"], c["nb"]
    r = jax.random.PRNGKey(0)
    q = jax.random.normal(r, (b, t, hkv * group, dh), dtype)
    knew = jax.random.normal(jax.random.fold_in(r, 1), (b, t, hkv, dh), dtype)
    vnew = jax.random.normal(jax.random.fold_in(r, 2), (b, t, hkv, dh), dtype)
    pool = (nb + 1, bs, hkv, dh) if layer is None else \
        (layers, nb + 1, bs, hkv, dh)
    kp = jax.random.normal(jax.random.fold_in(r, 3), pool, dtype)
    vp = jax.random.normal(jax.random.fold_in(r, 4), pool, dtype)
    tbl = tables(b, mb, nb)
    pos = jnp.asarray(positions, jnp.int32)
    win = jnp.int32(c["window"])
    kernel = jax.jit(lambda *a: paged_attn.paged_attention(
        *a, softcap=c["softcap"], page_chunk=pc, head_block=bh,
        interpret=True))
    if layer is None:
        fo, fk, fv = kernel(q, knew, vnew, kp, vp, tbl, pos, win)
        lk, lv = kp, vp
    else:
        fo, ak, av = kernel(q, knew, vnew, kp, vp, tbl, pos, win,
                            jnp.int32(layer))
        lk, lv = kp[layer], vp[layer]
        # the single-layer call on pool[layer] writes the same rows, bit
        # for bit, and every other layer's pages are untouched
        so, sk, sv = kernel(q, knew, vnew, lk, lv, tbl, pos, win)
        assert np.array_equal(np.asarray(fo), np.asarray(so))
        assert np.array_equal(np.asarray(ak[layer]), np.asarray(sk))
        assert np.array_equal(np.asarray(av[layer]), np.asarray(sv))
        others = [i for i in range(layers) if i != layer]
        assert np.array_equal(np.asarray(ak)[others], np.asarray(kp)[others])
        assert np.array_equal(np.asarray(av)[others], np.asarray(vp)[others])
        fk, fv = ak[layer], av[layer]
    ro, rk, rv = paged_attention_ref(q, knew, vnew, lk, lv, tbl, pos, win,
                                     c["softcap"])
    live = np.asarray(pos) < mb * bs
    tol = dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32 else \
        dict(atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(
        np.asarray(fo, np.float32)[live], np.asarray(ro, np.float32)[live],
        **tol)
    # pools must match BITWISE outside the trash page: the fused scatter is
    # the same write the gather path does, not an approximation of it
    assert np.array_equal(np.asarray(fk)[:-1], np.asarray(rk)[:-1])
    assert np.array_equal(np.asarray(fv)[:-1], np.asarray(rv)[:-1])


def test_vmem_budget_block_picker():
    blk = paged_attn.pick_block(hkv=8, dh=128, group=4, t=4, bs=16,
                                itemsize=2)
    assert blk is not None
    pc, bh = blk
    assert 8 % bh == 0
    assert paged_attn.paged_attn_vmem_bytes(
        bs=16, dh=128, group=4, t=4, pc=pc, bh=bh,
        itemsize=2) <= paged_attn.VMEM_BUDGET
    # an impossible shape has no in-budget block
    assert paged_attn.pick_block(hkv=8, dh=2 ** 16, group=4, t=4, bs=16,
                                 itemsize=4) is None
    # clamp keeps a legal block, repairs a head_block that no longer
    # divides hkv, and rejects like pick_block when nothing fits
    assert paged_attn.clamp_block((2, 8), hkv=4, dh=64, group=2, t=1,
                                  bs=16, itemsize=2)[1] <= 4
    assert paged_attn.clamp_block((2, 2), hkv=8, dh=2 ** 16, group=4, t=4,
                                  bs=16, itemsize=4) is None


# ---------------------------------------------------------------------------
# Dispatch routing (mirrors the cascade dispatch-counter tests).
# ---------------------------------------------------------------------------

def test_route_cpu_default_is_gather(monkeypatch):
    monkeypatch.setattr(paged_attn, "FORCE_FUSED", False)
    before = dict(ops.PAGED_ATTN_DISPATCHES)
    blk = ops.paged_attn_route(8, 64, 4, 1, 16, jnp.float32)
    if jax.default_backend() == "tpu":
        assert blk is not None
        assert ops.PAGED_ATTN_DISPATCHES["fused"] == before["fused"] + 1
    else:
        assert blk is None
        assert ops.PAGED_ATTN_DISPATCHES["gather"] == before["gather"] + 1


def test_route_forced_is_fused(monkeypatch):
    monkeypatch.setattr(paged_attn, "FORCE_FUSED", True)
    before = dict(ops.PAGED_ATTN_DISPATCHES)
    blk = ops.paged_attn_route(8, 64, 4, 1, 16, jnp.float32)
    assert blk is not None
    pc, bh = blk
    assert pc >= 1 and 8 % bh == 0
    assert ops.PAGED_ATTN_DISPATCHES["fused"] == before["fused"] + 1
    assert ops.PAGED_ATTN_DISPATCHES["gather"] == before["gather"]


def test_route_over_budget_falls_back(monkeypatch):
    monkeypatch.setattr(paged_attn, "FORCE_FUSED", True)
    monkeypatch.setattr(paged_attn, "clamp_block", lambda *a, **kw: None)
    before = dict(ops.PAGED_ATTN_DISPATCHES)
    assert ops.paged_attn_route(8, 64, 4, 1, 16, jnp.float32) is None
    assert ops.PAGED_ATTN_DISPATCHES["gather"] == before["gather"] + 1
    assert ops.PAGED_ATTN_DISPATCHES["fused"] == before["fused"]


# ---------------------------------------------------------------------------
# Engine-level stream identity, fused vs gather, all pageable families.
# ---------------------------------------------------------------------------

PAGED_ARCHS = ["qwen3_1_7b", "seamless_m4t_large_v2", "zamba2_1_2b"]

N_SLOTS, MAX_LEN, MAX_PROMPT, BLOCK = 2, 32, 12, 8


def _junk_draft_cfg(cfg):
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, n_layers=1, n_encoder_layers=1)
    return dataclasses.replace(cfg, n_layers=max(1, cfg.n_layers - 1))


@pytest.fixture(scope="module", params=PAGED_ARCHS)
def served_arch(request):
    cfg = registry.get_smoke_config(request.param)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)

    fes = [jax.random.normal(
               jax.random.fold_in(jax.random.PRNGKey(7), i),
               (1, cfg.n_frontend_tokens or 16, cfg.d_model))
           if cfg.family == "encdec" else None
           for i in range(3 * N_SLOTS)]

    def make_requests():
        rs = np.random.RandomState(1)
        return [Request(rid=i,
                        prompt=rs.randint(0, cfg.vocab_size,
                                          size=4 + i).tolist(),
                        max_new_tokens=5 + i % 3, frontend_embeds=fes[i])
                for i in range(3 * N_SLOTS)]   # 3x slots -> slot reuse

    dense_reqs = make_requests()
    Engine(model, cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
           max_prompt_len=MAX_PROMPT).run(dense_reqs, max_ticks=600)
    assert all(r.done for r in dense_reqs)
    return cfg, model, params, make_requests, dense_reqs


def _run_paged(arch, make_requests, fused, monkeypatch, *, n_blocks=None,
               spec=False, max_ticks=600):
    cfg, model, params = arch
    monkeypatch.setattr(paged_attn, "FORCE_FUSED", fused)
    kw = {}
    if spec:
        kw = dict(spec_k=2, draft=ModelDraft(_junk_draft_cfg(cfg),
                                             rng=jax.random.PRNGKey(9)))
    reqs = make_requests()
    eng = Engine(model, cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT, paged=True, block_size=BLOCK,
                 n_blocks=n_blocks, **kw)
    eng.run(reqs, max_ticks=max_ticks)
    return reqs, eng


def _assert_streams_equal(a, b, tag):
    for x, y in zip(a, b):
        assert y.generated == x.generated, (
            f"rid={x.rid} [{tag}]: {y.generated} != {x.generated}")
        assert y.finish_reason == x.finish_reason


def test_fused_decode_stream_identity(served_arch, monkeypatch):
    """Plain decode: fused and gather paged engines emit bit-identical
    greedy streams (and both match dense), with the dispatch counters
    recording that each run used the path it claims."""
    cfg, model, params, make_requests, dense_reqs = served_arch
    g_reqs, _ = _run_paged((cfg, model, params), make_requests, False,
                           monkeypatch)
    before = dict(ops.PAGED_ATTN_DISPATCHES)
    f_reqs, _ = _run_paged((cfg, model, params), make_requests, True,
                           monkeypatch)
    assert ops.PAGED_ATTN_DISPATCHES["fused"] > before["fused"]
    assert ops.PAGED_ATTN_DISPATCHES["gather"] == before["gather"]
    _assert_streams_equal(g_reqs, f_reqs, "decode fused-vs-gather")
    _assert_streams_equal(dense_reqs, f_reqs, "decode fused-vs-dense")


def test_fused_spec_verify_stream_identity(served_arch, monkeypatch):
    """Speculative verify (T = k+1 grid): same identity under a junk
    draft, so every rollback path crosses the fused kernel too."""
    cfg, model, params, make_requests, dense_reqs = served_arch
    g_reqs, _ = _run_paged((cfg, model, params), make_requests, False,
                           monkeypatch, spec=True)
    f_reqs, eng = _run_paged((cfg, model, params), make_requests, True,
                             monkeypatch, spec=True)
    _assert_streams_equal(g_reqs, f_reqs, "spec fused-vs-gather")
    _assert_streams_equal(dense_reqs, f_reqs, "spec fused-vs-dense")
    assert eng.stats["drafted"] > 0
    assert eng.allocator.in_use == 0


def test_page_recycling_no_stale_kv(served_arch, monkeypatch):
    """Pool of 5 pages for 6 requests needing ~12: every page is freed by
    an eviction and LIFO-remapped to a DIFFERENT slot mid-stream, so any
    stale K/V leaking past the causal/frontier mask in the fused kernel
    would corrupt the later streams.  Dense parity pins it down."""
    cfg, model, params, make_requests, dense_reqs = served_arch
    f_reqs, eng = _run_paged((cfg, model, params), make_requests, True,
                             monkeypatch, n_blocks=5, max_ticks=1200)
    _assert_streams_equal(dense_reqs, f_reqs, "recycled pages")
    assert eng.stats["preempted"] == 0
    assert eng.allocator.peak_in_use <= 5
    # reuse actually happened: the run needed more page-mappings than the
    # pool holds, so completion implies evict->admit recycling
    total_pages_needed = sum(-(-(r.prompt_len + len(r.generated)) // BLOCK)
                             for r in f_reqs)
    assert total_pages_needed > 5
