"""Speculative decoding: the tentpole acceptance criteria.

* greedy spec streams are BIT-IDENTICAL to the non-speculative engine for
  every pageable family, dense AND paged — including under a garbage
  draft (maximal rollback, crossing page boundaries) and a perfect draft
  (full acceptance, bonus-token path);
* rejection sampling preserves the target sampling distribution;
* truncated-cascade self-drafting: acceptance > 0.5 at half depth on the
  ACDC smoke model and monotone in draft depth;
* rollback plumbing: allocator verify-window mapping and tail-page trim,
  the paged admission lookahead window, and the stalled-slot SSM freeze.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal install
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import registry
from repro.models import get_model
from repro.serving import BlockAllocator, Engine, Request, Scheduler
from repro.spec import ModelDraft, TruncatedCascadeDraft
from repro.spec import verify as verify_mod
from repro.spec.draft import truncate_cascades

SPEC_ARCHS = ["qwen3_1_7b", "seamless_m4t_large_v2", "zamba2_1_2b"]

N_SLOTS, MAX_LEN, MAX_PROMPT, SPEC_K = 2, 40, 16, 3


def _junk_draft_cfg(cfg):
    """A cheap draft config whose logits genuinely differ from the target
    (fresh params, fewer layers) — maximal rejection/rollback stress."""
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, n_layers=1, n_encoder_layers=1)
    return dataclasses.replace(cfg, n_layers=max(1, cfg.n_layers - 1))


@pytest.fixture(scope="module", params=SPEC_ARCHS)
def served_arch(request):
    cfg = registry.get_smoke_config(request.param)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)

    rs = np.random.RandomState(0)
    shapes = [(int(rs.randint(3, MAX_PROMPT)), int(rs.randint(3, 9)))
              for _ in range(3 * N_SLOTS)]   # 3x slots -> slot reuse
    fes = [jax.random.normal(
               jax.random.fold_in(jax.random.PRNGKey(7), i),
               (1, cfg.n_frontend_tokens or 16, cfg.d_model))
           if cfg.family == "encdec" else None
           for i in range(len(shapes))]

    def make_requests():
        rs2 = np.random.RandomState(1)
        return [Request(rid=i,
                        prompt=rs2.randint(0, cfg.vocab_size,
                                           size=plen).tolist(),
                        max_new_tokens=budget, frontend_embeds=fes[i])
                for i, (plen, budget) in enumerate(shapes)]

    dense_reqs = make_requests()
    eng = Engine(model, cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT)
    eng.run(dense_reqs, max_ticks=600)
    assert all(r.done for r in dense_reqs)
    return cfg, model, params, make_requests, dense_reqs


def _assert_streams_equal(reqs, dense_reqs, tag):
    for d, s in zip(dense_reqs, reqs):
        assert s.generated == d.generated, (
            f"rid={d.rid} [{tag}]: spec {s.generated} != "
            f"dense {d.generated}")
        assert s.finish_reason == d.finish_reason


def test_spec_greedy_bit_identical_dense(served_arch):
    """Garbage draft, dense cache: every rejection rolls the slot back and
    the committed stream must still equal non-speculative greedy."""
    cfg, model, params, make_requests, dense_reqs = served_arch
    reqs = make_requests()
    eng = Engine(model, cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT, spec_k=SPEC_K,
                 draft=ModelDraft(_junk_draft_cfg(cfg),
                                  rng=jax.random.PRNGKey(9)))
    eng.run(reqs, max_ticks=600)
    _assert_streams_equal(reqs, dense_reqs, "dense")
    assert eng.stats["drafted"] > 0


def test_spec_greedy_bit_identical_paged(served_arch):
    """Same under paging with 4-token pages: the k+1 verify window spans
    page boundaries every tick, so rollback repeatedly returns partially
    written tail pages to the pool."""
    cfg, model, params, make_requests, dense_reqs = served_arch
    reqs = make_requests()
    eng = Engine(model, cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT, paged=True, block_size=4,
                 spec_k=SPEC_K,
                 draft=ModelDraft(_junk_draft_cfg(cfg),
                                  rng=jax.random.PRNGKey(9)))
    eng.run(reqs, max_ticks=600)
    _assert_streams_equal(reqs, dense_reqs, "paged")
    assert eng.stats["preempted"] == 0
    # rollback returned every over-mapped page: nothing leaks at drain
    assert eng.allocator.in_use == 0


def test_spec_perfect_draft_full_acceptance(served_arch):
    """A draft that IS the target accepts every token (the bonus-token
    path) and needs far fewer verify dispatches than tokens emitted."""
    cfg, model, params, make_requests, dense_reqs = served_arch
    reqs = make_requests()
    eng = Engine(model, cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT, spec_k=SPEC_K,
                 draft=ModelDraft(cfg, params=params))
    eng.run(reqs, max_ticks=600)
    _assert_streams_equal(reqs, dense_reqs, "perfect")
    assert eng.stats["acceptance_rate"] == 1.0
    decode_tokens = eng.stats["tokens_out"] - len(reqs)  # minus prefill toks
    assert eng.stats["decode_ticks"] < decode_tokens


def test_spec_greedy_bit_identical_mamba2_dense():
    """The pure-SSM family has no paged cache but does have a verify path:
    dense spec decode with a garbage mamba2 draft must stay bit-identical
    (covers mamba2.verify_step on BOTH the target and the draft side —
    snapshot assembly, accepted-length commit, parked-row zero-commit)."""
    cfg = registry.get_smoke_config("mamba2_1_3b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(2)
    mk = lambda: [Request(rid=i,
                          prompt=rs.randint(0, cfg.vocab_size,
                                            size=4 + i).tolist(),
                          max_new_tokens=5 + i)
                  for i in range(4)]
    rs = np.random.RandomState(2)
    dense_reqs = mk()
    rs = np.random.RandomState(2)
    reqs = mk()
    Engine(model, cfg, params, n_slots=2, max_len=MAX_LEN,
           max_prompt_len=MAX_PROMPT).run(dense_reqs, max_ticks=400)
    eng = Engine(model, cfg, params, n_slots=2, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT, spec_k=SPEC_K,
                 draft=ModelDraft(_junk_draft_cfg(cfg),
                                  rng=jax.random.PRNGKey(9)))
    eng.run(reqs, max_ticks=400)
    _assert_streams_equal(reqs, dense_reqs, "mamba2")
    assert eng.stats["drafted"] > 0


def test_engine_draft_depth_zero_not_silently_defaulted():
    """`draft_depth=0` must surface the depth validation error, not be
    swallowed as falsy and replaced by the half-depth default."""
    cfg = dataclasses.replace(registry.get_smoke_config("qwen3_1_7b"),
                              sell_kind="acdc", sell_k=4,
                              sell_permute=False)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="depth 0"):
        Engine(model, cfg, params, n_slots=1, max_len=32, max_prompt_len=8,
               spec_k=2, draft_depth=0)


# ---------------------------------------------------------------------------
# Truncated-cascade self-drafting (the paper's depth result as a draft).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def acdc_target():
    """ACDC SELL smoke model (un-riffled, near-converged init scale — a
    trained cascade's tail is near identity, which is exactly what makes
    truncation a usable draft; riffled cascades truncate poorly, see
    spec/draft.py)."""
    cfg = dataclasses.replace(
        registry.get_smoke_config("qwen3_1_7b"), sell_kind="acdc",
        sell_k=4, sell_permute=False, sell_init_std=0.02)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(0)
    shapes = [(int(rs.randint(4, MAX_PROMPT)), 10) for _ in range(4)]

    def make_requests():
        rs2 = np.random.RandomState(1)
        return [Request(rid=i,
                        prompt=rs2.randint(0, cfg.vocab_size,
                                           size=plen).tolist(),
                        max_new_tokens=budget)
                for i, (plen, budget) in enumerate(shapes)]

    dense_reqs = make_requests()
    eng = Engine(model, cfg, params, n_slots=2, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT)
    eng.run(dense_reqs, max_ticks=600)
    return cfg, model, params, make_requests, dense_reqs


def _acceptance_at_depth(acdc_target, depth):
    cfg, model, params, make_requests, dense_reqs = acdc_target
    reqs = make_requests()
    eng = Engine(model, cfg, params, n_slots=2, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT, spec_k=4,
                 draft=TruncatedCascadeDraft(cfg, params, depth=depth))
    eng.run(reqs, max_ticks=600)
    _assert_streams_equal(reqs, dense_reqs, f"depth={depth}")
    return eng.stats["acceptance_rate"]


def test_truncated_cascade_half_depth_acceptance(acdc_target):
    """The acceptance criterion: K_draft = K/2 accepts > 0.5 of drafts."""
    assert _acceptance_at_depth(acdc_target, 2) > 0.5


def test_truncated_cascade_acceptance_monotone_in_depth(acdc_target):
    """Deeper truncations approximate the target better (sections 3-4
    depth result): on the target's own streams (teacher forcing), the
    draft's logit error falls strictly with depth, to zero at full depth,
    where the draft IS the target and a speculative run accepts every
    draft.

    Acceptance itself is not monotone at this size.  One speculative run
    is a few dozen drafts whose contexts depend on earlier acceptances:
    over parameter seeds 0-3 depth 1 out-accepted depth 2 in two runs.
    Even the teacher-forced greedy agreement over these ~75 positions
    went 0.88, 0.84, 0.93, 1.0 for depths 1-4, while the logit error fell
    with depth for every seed tried."""
    cfg, model, params, _, dense_reqs = acdc_target
    width = max(r.prompt_len + len(r.generated) for r in dense_reqs)
    tokens = np.zeros((len(dense_reqs), width), np.int32)
    valid = np.zeros((len(dense_reqs), width), bool)
    for i, r in enumerate(dense_reqs):
        seq = list(r.prompt) + list(r.generated)
        tokens[i, :len(seq)] = seq
        valid[i, :len(seq)] = True
    target = model.apply(params, jnp.asarray(tokens), cfg)[valid]
    errors = []
    for depth in range(1, cfg.sell_k + 1):
        dcfg = dataclasses.replace(cfg, sell_k=depth)
        logits = get_model(dcfg).apply(truncate_cascades(params, depth),
                                       jnp.asarray(tokens), dcfg)[valid]
        errors.append(float(jnp.linalg.norm(logits - target)
                            / jnp.linalg.norm(target)))
    assert all(e > f for e, f in zip(errors, errors[1:])), errors
    assert errors[-1] == 0.0
    assert _acceptance_at_depth(acdc_target, cfg.sell_k) == 1.0


def test_truncated_cascade_skip_top_layers(acdc_target):
    """skip_layers drops top transformer blocks from the draft on top of
    cascade truncation; streams stay exact regardless."""
    cfg, model, params, make_requests, dense_reqs = acdc_target
    reqs = make_requests()
    draft = TruncatedCascadeDraft(cfg, params, depth=2, skip_layers=1)
    assert draft.cfg.n_layers == cfg.n_layers - 1
    eng = Engine(model, cfg, params, n_slots=2, max_len=MAX_LEN,
                 max_prompt_len=MAX_PROMPT, spec_k=3, draft=draft)
    eng.run(reqs, max_ticks=600)
    _assert_streams_equal(reqs, dense_reqs, "skip_layers")


def test_model_draft_rejects_vocab_mismatch():
    cfg = registry.get_smoke_config("qwen3_1_7b")
    other = dataclasses.replace(cfg, vocab_size=cfg.vocab_size + 1)
    with pytest.raises(ValueError, match="vocab"):
        ModelDraft(other, rng=jax.random.PRNGKey(0), target_cfg=cfg)


# ---------------------------------------------------------------------------
# Rejection sampling preserves the target distribution.
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=0, max_value=2**16))
def test_rejection_sampling_preserves_target_distribution(seed):
    """Whatever the draft proposes, the FIRST committed token of a spec
    step is distributed as the target: run the accept/resample math over
    thousands of independent keys (vectorized as batch rows) with drafts
    genuinely sampled from the draft distribution, and compare the
    empirical marginal to the target softmax in total variation."""
    vocab, k, n_rows = 5, 2, 4000
    rng = np.random.RandomState(seed)
    t_logits = jnp.asarray(
        np.broadcast_to(rng.randn(1, k + 1, vocab) * 1.5,
                        (n_rows, k + 1, vocab)))
    d_logits = jnp.asarray(
        np.broadcast_to(rng.randn(1, k, vocab) * 1.5,
                        (n_rows, k, vocab)))
    key = jax.random.PRNGKey(seed)
    dk, ak = jax.random.split(key)
    # drafts MUST be samples from the draft distribution (the algorithm's
    # precondition): one independent draw per row and position
    drafts = jax.random.categorical(
        dk, jnp.broadcast_to(d_logits, (n_rows, k, vocab)),
        axis=-1).astype(jnp.int32)
    # independent accept/resample randomness per row
    n, nxt = jax.vmap(
        lambda r, lg, dlg, dr: verify_mod.rejection_accept(
            r, lg[None], dlg[None], dr[None]),
    )(jax.random.split(ak, n_rows), t_logits, d_logits, drafts)
    n = np.asarray(n)[:, 0]
    nxt = np.asarray(nxt)[:, 0]
    drafts_np = np.asarray(drafts)
    first = np.where(n >= 1, drafts_np[:, 0], nxt)
    emp = np.bincount(first, minlength=vocab) / n_rows
    target = np.asarray(jax.nn.softmax(t_logits[0, 0]))
    tv = 0.5 * np.abs(emp - target).sum()
    assert tv < 0.06, f"total variation {tv:.3f} (emp={emp}, p={target})"


def test_greedy_accept_math():
    """Unit pin of the prefix-match rule and correction/bonus selection."""
    logits = jnp.asarray(np.eye(4, dtype=np.float32)[
        np.array([[2, 0, 3, 1], [1, 2, 0, 3]])])       # argmax per position
    drafts = jnp.asarray([[2, 0, 0], [0, 2, 0]], jnp.int32)
    n, nxt = verify_mod.greedy_accept(logits, drafts)
    # row 0: d1=2==argmax(L0), d2=0==argmax(L1), d3=0!=argmax(L2)=3 -> n=2
    # row 1: d1=0!=argmax(L0)=1 -> n=0, correction=argmax(L0)=1
    assert n.tolist() == [2, 0]
    assert nxt.tolist() == [3, 1]
    out = verify_mod.committed_tokens(drafts, n, nxt)
    assert out[0, :3].tolist() == [2, 0, 3]
    assert out[1, 0].tolist() == 1


# ---------------------------------------------------------------------------
# Rollback plumbing: allocator, scheduler lookahead, stall freeze.
# ---------------------------------------------------------------------------

def test_allocator_ensure_range_all_or_nothing():
    a = BlockAllocator(n_blocks=4, block_size=4, n_slots=2,
                       max_blocks_per_slot=4)
    a.alloc_slot(0, 7)                     # pages 0..1 (positions 0..7)
    assert a.n_free == 2
    # verify window 8..12 needs pages 2 and 3: both free -> mapped
    assert a.ensure_range(0, 8, 5)
    assert a.blocks_held(0) == 4 and a.n_free == 0
    a.free_slot(0)
    a.alloc_slot(0, 7)
    a.alloc_slot(1, 7)                     # pool empty again
    # window needs 2 pages, 0 free: nothing may stick
    assert not a.ensure_range(0, 8, 5)
    assert a.blocks_held(0) == 2 and a.n_free == 0
    # beyond the virtual row length needs no mapping
    assert a.ensure_range(0, 4 * 4, 3)


def test_allocator_trim_returns_tail_pages():
    a = BlockAllocator(n_blocks=6, block_size=4, n_slots=1,
                       max_blocks_per_slot=6)
    a.alloc_slot(0, 7)                     # 2 pages
    assert a.ensure_range(0, 8, 8)         # verify window maps pages 2,3
    assert a.blocks_held(0) == 4
    # commit lands at 10 tokens -> ceil(10/4)=3 pages stay, 1 returns
    assert a.trim_slot(0, 10) == 1
    assert a.blocks_held(0) == 3 and a.n_free == 3
    # trimming an already-tight slot is a no-op
    assert a.trim_slot(0, 10) == 0
    # freed page is immediately remappable
    assert a.ensure(0, 12)
    # engine convention: trim at frontier+1 so a page-boundary frontier
    # keeps the page its next write needs instead of churning it
    assert a.trim_slot(0, 13) == 0
    assert a.trim_slot(0, 12) == 1


def test_scheduler_lookahead_window_unblocks_small_requests():
    """A capacity-blocked head no longer starves the queue: the first of
    the next W queued requests that fits is admitted; beyond the window
    nothing is considered; queue order is otherwise preserved."""
    fits = lambda r: r.prompt_len <= 4
    sch = Scheduler(2, admit_ok=fits, window=3)
    big = Request(rid=0, prompt=[1] * 10)
    small1 = Request(rid=1, prompt=[1] * 3)
    small2 = Request(rid=2, prompt=[1] * 3)
    for r in (big, small1, small2):
        sch.submit(r)
    admitted = sch.admit(limit=1)
    assert [r.rid for _, r in admitted] == [1]     # head skipped, not lost
    assert [r.rid for r in sch.queue] == [0, 2]
    # window=1 restores strict FIFO blocking
    sch2 = Scheduler(2, admit_ok=fits, window=1)
    for r in (Request(rid=0, prompt=[1] * 10), Request(rid=1, prompt=[1] * 3)):
        sch2.submit(r)
    assert sch2.admit() == []
    # beyond the window nothing is admitted either
    sch3 = Scheduler(2, admit_ok=fits, window=2)
    for rid, plen in ((0, 10), (1, 10), (2, 3)):
        sch3.submit(Request(rid=rid, prompt=[1] * plen))
    assert sch3.admit() == []


def test_paged_admission_no_head_of_line_blocking():
    """End-to-end regression: a large head request that does not fit the
    free pool no longer starves smaller ones behind it — they are served
    first and the head completes once pages free up."""
    cfg = registry.get_smoke_config("qwen3_1_7b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    # pool of 4 4-token pages; the 12-token request needs all 4 at once
    eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                 max_prompt_len=12, paged=True, block_size=4, n_blocks=4)
    first = Request(rid=0, prompt=[3, 1, 4], max_new_tokens=2)
    big = Request(rid=1, prompt=list(range(1, 13)), max_new_tokens=2)
    small = Request(rid=2, prompt=[2, 7, 1], max_new_tokens=2)
    for r in (first, big, small):
        eng.submit(r)
    eng.tick()
    # `first` holds a page, so `big` (queue head) cannot map its 4 — but
    # `small` behind it is admitted instead of waiting on the head
    assert small.status.value == "active" or small.done
    assert big.status.value == "queued"            # skipped, not starved out
    ticks = 0
    while eng.scheduler.has_work:
        eng.tick()
        ticks += 1
        assert ticks < 200
    assert big.done and small.done and first.done
    assert eng.stats["preempted"] == 0


def test_zamba2_stalled_slot_freezes_ssm_state():
    """Regression: a stalled paged slot parks its KV write on the trash
    page but used to keep advancing its Mamba SSM/conv state, consuming
    the pending token twice once the stall cleared.  The stream after a
    real stall must equal the dense engine's."""
    cfg = registry.get_smoke_config("zamba2_1_2b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    mk = lambda: [Request(rid=0, prompt=list(range(1, 6)), max_new_tokens=6),
                  Request(rid=1, prompt=list(range(1, 8)), max_new_tokens=6)]
    dense = mk()
    Engine(model, cfg, params, n_slots=2, max_len=40,
           max_prompt_len=16).run(dense, max_ticks=600)
    paged = mk()
    eng = Engine(model, cfg, params, n_slots=2, max_len=40,
                 max_prompt_len=16, paged=True, block_size=4, n_blocks=5)
    eng.run(paged, max_ticks=1200)
    assert eng.stats["stalled_slot_ticks"] > 0, "scenario must stall"
    assert eng.stats["preempted"] == 0
    for d, p in zip(dense, paged):
        assert p.generated == d.generated, (
            f"rid={d.rid}: stalled stream {p.generated} != {d.generated}")
