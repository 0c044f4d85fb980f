"""Continuous-batching serving engine over the model zoo's decode caches.

The engine owns a fixed-shape cache with ``n_slots`` batch rows and runs a
tick loop:

1. **admit** — while a slot is free and requests are queued, the most
   urgent request (earliest deadline, then priority, then arrival order)
   is admitted: ONE lowered prefill program runs its whole (right-padded)
   prompt, the resulting per-slot KV / SSM state is scattered into the
   slot's cache row, and the first token is sampled from the
   last-position logits (this is also the time-to-first-token mark);
2. **decode** — one fused decode step advances EVERY active slot by one
   token; free slots ride along parked at the row length where the cache
   scatter writes nothing;
3. **evict** — requests that hit EOS, their ``max_new_tokens`` budget,
   the cache ceiling, or their deadline release their slot immediately,
   so the next tick's admission refills the batch.

All shapes are static — prompts pad to ``max_prompt_len``, the decode batch
is always ``n_slots`` wide — so the engine compiles exactly two programs
(one prefill, one decode) regardless of traffic.  Per-request compute is
batch-row-independent (each slot attends only to its own cache row), so a
request's output stream is identical to running it alone; the engine test
pins that down.

Paged mode (``paged=True``) swaps the dense per-slot ``max_len`` slabs for
a global pool of ``block_size``-token pages managed by
:class:`repro.serving.blocks.BlockAllocator`: admission is gated on free
blocks for the prompt plus one decode token, decode growth maps pages
lazily, and a slot whose next page cannot be mapped *stalls* (parks for
the tick, producing nothing) until an eviction frees pages — so the pool
can be sized for the traffic mix instead of ``n_slots * max_len`` while
greedy output streams stay identical to the dense cache.

**Preemption with recompute**: when every active slot is stalled at once
(deadlock), or a deadline demands the capacity, the victim slot's pages
are released and the request is *requeued* — its generated-so-far tokens
fold into the re-prefill context at readmission, so a greedy stream
continues bit-identically to an undisturbed run (prefill and decode agree
position-for-position; pinned by tests/test_serving_resilience.py).  A
per-request ``max_preemptions`` budget with exponential tick backoff
bounds the retries; past it the request finishes with
``finish_reason="preempted_limit"``.  The same requeue path heals
corrupt decode output (non-finite logits produce out-of-range sample
ids, which the host-side validity guard catches).

**Deadline-aware scheduling**: requests carry ``deadline_s`` / priority;
admission is earliest-deadline-first with aging (see ``scheduler.py``),
queued requests past their deadline are swept to
``finish_reason="timeout"`` without burning a prefill, active ones are
evicted on expiry, and a queued request about to miss its deadline may
preempt-with-requeue the active request with the most slack.

**Graceful degradation**: a tick-latency watchdog
(:class:`repro.dist.elastic.StragglerMonitor`) plus pool-pressure and
queue-depth signals drive a reversible ladder — shrink ``spec_k``, then
disable speculation, then bound the admission queue and shed the
lowest-priority arrivals (``finish_reason="rejected"``) — stepping back
up after sustained calm.  Every transition and shed is counted in
``stats``; ladder moves never change greedy token streams (speculation
is exact and shedding only drops whole requests).

Speculative mode (``spec_k > 0``) replaces the one-token decode tick with
draft -> verify -> accept/rollback: a cheap draft source
(:mod:`repro.spec.draft`, default the target's own truncated ACDC
cascades) proposes ``spec_k`` tokens per slot in one fused program, ONE
target verify program scores and commits them
(:func:`repro.dist.steps.make_verify_step`), and each slot advances by
its accepted length — variable per slot, shapes static via masking.
Greedy streams stay bit-identical to the non-speculative engine; see
:mod:`repro.serving` for the tick contract.

Fault injection (``fault=FaultPlan(...)``) threads a deterministic
seed-driven chaos schedule behind a no-op default into the allocator and
the tick loop; see :mod:`repro.serving.faults`.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import steps as steps_mod
from repro.dist.elastic import StragglerMonitor
from repro.obs import Observability
from repro.obs.metrics import StatsView
from repro.serving import sampler as sampler_mod
from repro.serving.blocks import BlockAllocator
from repro.serving.faults import FaultPlan
from repro.serving.request import Request, RequestStatus
from repro.serving.scheduler import Scheduler

#: ``Engine.stats`` key -> (registry metric name, kind).  Kinds:
#: ``counter`` (int-valued), ``seconds`` (float counter), ``gauge``,
#: ``derived`` (computed at read/snapshot time — never stored, so it can
#: never go stale).  The prose cross-reference lives in
#: ``repro/serving/__init__.py``; the glossary in ``repro/obs/__init__``.
STATS_METRICS = {
    "prefill_dispatches": ("serve_prefill_dispatches_total", "counter"),
    "decode_ticks": ("serve_decode_ticks_total", "counter"),
    "tokens_out": ("serve_tokens_out_total", "counter"),
    "finished": ("serve_finished_total", "counter"),
    "preempted": ("serve_preempted_total", "counter"),
    "requeued": ("serve_requeued_total", "counter"),
    "timeout": ("serve_timeout_total", "counter"),
    "rejected": ("serve_rejected_total", "counter"),
    "deadline_preempts": ("serve_deadline_preempts_total", "counter"),
    "corrupt_ticks": ("serve_corrupt_ticks_total", "counter"),
    "stalled_slot_ticks": ("serve_stalled_slot_ticks_total", "counter"),
    "degrade_level": ("serve_degrade_level", "gauge"),
    "degrade_down": ("serve_degrade_down_total", "counter"),
    "degrade_up": ("serve_degrade_up_total", "counter"),
    "prefill_s": ("serve_prefill_seconds_total", "seconds"),
    "decode_s": ("serve_decode_seconds_total", "seconds"),
    "drafted": ("serve_spec_drafted_total", "counter"),
    "accepted": ("serve_spec_accepted_total", "counter"),
    "acceptance_rate": ("serve_acceptance_rate", "derived"),
}


class Engine:
    def __init__(
        self,
        model,
        cfg,
        params,
        n_slots: int = 4,
        max_len: int = 128,
        max_prompt_len: Optional[int] = None,
        sample: str = "greedy",
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 0.0,
        rng: Optional[jax.Array] = None,
        paged: bool = False,
        block_size: int = 16,
        n_blocks: Optional[int] = None,
        admit_window: int = 4,
        age_limit: int = 16,
        spec_k: int = 0,
        draft=None,
        draft_depth: Optional[int] = None,
        draft_skip_layers: int = 0,
        clock: Optional[Callable[[], float]] = None,
        fault: Optional[FaultPlan] = None,
        obs: Optional[Observability] = None,
        deadline_margin_s: float = 0.05,
        queue_bound: Optional[int] = None,
        degrade_down_after: int = 3,
        degrade_up_after: int = 12,
    ):
        if model.prefill is None or model.decode_step is None:
            raise ValueError(f"family {cfg.family!r} cannot serve")
        if paged and (model.init_cache_paged is None
                      or model.decode_step_paged is None):
            raise ValueError(
                f"family {cfg.family!r} has no paged KV cache (its decode "
                "state is not length-proportional); serve it dense")
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 disables)")
        self.model = model
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.max_prompt_len = max_prompt_len or max_len // 2
        self.paged = paged
        self._clock = clock if clock is not None else time.time
        # duration source: wall time by default, the INJECTED clock when
        # one is supplied — a virtual-clock chaos run then produces fully
        # deterministic tick/prefill/decode timings, which is what makes
        # trace and snapshot replays byte-identical (tests/test_obs.py)
        self._timer = clock if clock is not None else time.perf_counter
        self._fault = fault
        self.deadline_margin_s = deadline_margin_s
        self.queue_bound = queue_bound if queue_bound is not None \
            else 4 * n_slots
        self._rng = jax.random.PRNGKey(0) if rng is None else rng
        # disjoint RNG streams: decode-tick keys chain through fold_in(_, 0)
        # and admission keys through fold_in(_, 1), so a tick counter can
        # never collide with a request id (bit-packing both into one fold
        # value was non-injective: tick 2**20 reused tick 0's key and
        # rid >= 2**20 collided with decode keys)
        self._rng_decode = jax.random.fold_in(self._rng, 0)
        self._rng_admit = jax.random.fold_in(self._rng, 1)

        if paged:
            self.block_size = block_size
            self.max_blocks = -(-max_len // block_size)
            # virtual per-slot row length: max_len rounded up to whole
            # pages; the engine still stops requests at max_len, the tail
            # padding just keeps the page-wise gather rectangular
            self._virtual = self.max_blocks * block_size
            if n_blocks is None:
                n_blocks = n_slots * self.max_blocks  # dense-parity pool
            min_pool = -(-(self.max_prompt_len + 1) // block_size)
            if n_blocks < min_pool:
                raise ValueError(
                    f"pool of {n_blocks} blocks cannot admit a "
                    f"max_prompt_len={self.max_prompt_len} request "
                    f"(needs {min_pool})")
            self.allocator = BlockAllocator(n_blocks, block_size, n_slots,
                                            self.max_blocks, fault=fault)
            # capacity check on ctx_len, not prompt_len: a requeued
            # request re-prefills its prompt PLUS generated-so-far tokens
            self.scheduler = Scheduler(
                n_slots,
                admit_ok=lambda r: self.allocator.can_admit(r.ctx_len),
                window=admit_window, age_limit=age_limit)
            self._park = self._virtual
            self._cache = model.init_cache_paged(cfg, n_slots, n_blocks,
                                                 block_size)
            # batch-1 dense template the admission prefill writes through
            # before the in-program page scatter
            self._slot_template = model.init_cache(cfg, 1, self._virtual)
            self._prefill = jax.jit(steps_mod.make_prefill_step(
                model, cfg, paged=True), donate_argnums=(1,))
            self._decode = jax.jit(steps_mod.make_serve_step(
                model, cfg, sample=sample, temperature=temperature,
                top_k=top_k, top_p=top_p, paged=True), donate_argnums=(1,))
            self._insert = None
        else:
            self.allocator = None
            self.scheduler = Scheduler(n_slots, age_limit=age_limit)
            self._park = max_len
            self._cache = model.init_cache(cfg, n_slots, max_len)
            # template for per-admission prefill: batch-1, same max_len slabs
            self._slot_template = model.init_cache(cfg, 1, max_len)
            # the big cache is donated through decode/insert: it is the
            # dominant serving allocation and both calls replace
            # self._cache wholesale, so XLA can update the buffers in
            # place instead of copying the whole multi-layer slab per tick
            self._prefill = jax.jit(steps_mod.make_prefill_step(model, cfg))
            self._decode = jax.jit(steps_mod.make_serve_step(
                model, cfg, sample=sample, temperature=temperature,
                top_k=top_k, top_p=top_p), donate_argnums=(1,))
            self._insert = steps_mod.make_insert_step()

        self._tokens = np.zeros((n_slots,), np.int32)
        self._positions = np.full((n_slots,), self._park, np.int32)
        self._stalled: Set[int] = set()
        self._sample = jax.jit(functools.partial(
            sampler_mod.sample, method=sample, temperature=temperature,
            top_k=top_k, top_p=top_p))
        # observability: the registry is ALWAYS live (it backs the
        # back-compat ``stats`` view); tracing / export / profiling are
        # optional surfaces, each a single None-check when off — the
        # documented noop path (see repro/obs/__init__.py).  An
        # Observability bundle must not be shared between engines: the
        # get-or-create registry would silently merge their stats.
        self.obs = obs if obs is not None else Observability.off()
        self._tracer = self.obs.tracer
        if self._tracer is not None and self._tracer.clock is None:
            self._tracer.clock = self._clock  # adopt the engine clock
        self._obs_tick = self.obs.tick_hook()
        self._prof = self.obs.prof
        self.stats = self._build_stats()
        reg = self.obs.registry
        self._h_ttft = reg.histogram(
            "serve_ttft_seconds", "submit -> first token latency")
        self._h_tpot = reg.histogram(
            "serve_tpot_seconds",
            "per-output-token decode latency: (t_finish - ttft)/(n-1)")
        self._h_tick = reg.histogram(
            "serve_tick_seconds", "engine tick wall latency")
        self.wall_clock_exceeded = False
        # preempted requests wait out an exponential backoff (in ticks)
        # before re-entering the queue: (eligible_tick, request)
        self._backoff: List[Tuple[int, Request]] = []
        self._tick_no = 0

        self.spec_k = spec_k
        self.spec_k_eff = spec_k
        self.draft = None
        if spec_k:
            vfn = model.verify_step_paged if paged else model.verify_step
            if vfn is None:
                raise ValueError(
                    f"family {cfg.family!r} has no "
                    f"{'paged ' if paged else ''}speculative verify path")
            if draft is None:
                # paper-native default: the target's own cascades truncated
                # to half depth (sections 3-4 depth result)
                from repro.spec.draft import TruncatedCascadeDraft
                depth = (draft_depth if draft_depth is not None
                         else max(1, cfg.sell_k // 2))
                draft = TruncatedCascadeDraft(cfg, params, depth=depth,
                                              skip_layers=draft_skip_layers)
            self.draft = draft
            self.draft.prepare(n_slots, self.max_len, spec_k, sample,
                               temperature, top_k, top_p)
            self._verify = jax.jit(steps_mod.make_verify_step(
                model, cfg, sample=sample, temperature=temperature,
                top_k=top_k, top_p=top_p, paged=paged, park=self._park),
                donate_argnums=(1,))

        # graceful-degradation ladder: reversible step-downs ordered
        # cheapest-first (shrinking speculation costs acceptance rate,
        # never tokens), with request shedding strictly last
        self._levels = ["full"]
        if spec_k >= 2:
            self._levels.append("spec_half")
        if spec_k >= 1:
            self._levels.append("spec_off")
        self._levels.append("shed")
        self._level = 0
        self._hot = 0
        self._calm = 0
        self.degrade_down_after = degrade_down_after
        self.degrade_up_after = degrade_up_after
        self._watchdog = StragglerMonitor(alpha=0.2, factor=3.0, warmup=3,
                                          adapt_after=5)

    # -- accounting --------------------------------------------------------

    def _build_stats(self) -> StatsView:
        """Bind every historical ``stats`` key to its registry metric
        (table: ``STATS_METRICS``).  ``acceptance_rate`` is DERIVED —
        computed from the drafted/accepted counters at read time — which
        fixes the seed's staleness bug: the stored ratio was only
        refreshed inside the spec tick while ``drafted`` grew, so a run
        degraded to ``spec_off`` kept reporting its pre-degradation
        value forever."""
        reg = self.obs.registry
        view = StatsView()
        for key, (name, kind) in STATS_METRICS.items():
            if kind == "counter":
                m = reg.counter(name)
                view.bind(key, lambda m=m: int(m.value), m.set)
            elif kind == "seconds":
                m = reg.counter(name)
                view.bind(key, lambda m=m: float(m.value), m.set)
            elif kind == "gauge":
                m = reg.gauge(name)
                view.bind(key, lambda m=m: int(m.value), m.set)
        drafted = reg.counter(STATS_METRICS["drafted"][0])
        accepted = reg.counter(STATS_METRICS["accepted"][0])
        rate = reg.derived_gauge(
            STATS_METRICS["acceptance_rate"][0],
            lambda: (accepted.value / drafted.value) if drafted.value
            else 0.0,
            "accepted/drafted, computed at snapshot time (never stale)")
        view.bind("acceptance_rate", rate)
        return view

    @property
    def cache_bytes(self) -> int:
        """Bytes held by the decode cache (the dominant serving
        allocation): dense slabs or the paged pool, whichever is live —
        plus the draft's dense slot cache in speculative mode, so the
        self-draft's memory cost stays visible next to a paged pool."""
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(self._cache))
        if self.draft is not None:
            total += self.draft.cache_bytes
        return total

    def _decode_rng(self, tick: int) -> jax.Array:
        return jax.random.fold_in(self._rng_decode, tick)

    def _admit_rng(self, rid: int) -> jax.Array:
        return jax.random.fold_in(self._rng_admit, rid)

    # -- submission -------------------------------------------------------

    def submit(self, request: Request) -> None:
        if request.prompt_len < 1:
            raise ValueError(f"request {request.rid}: empty prompt")
        if request.prompt_len > self.max_prompt_len:
            raise ValueError(
                f"request {request.rid}: prompt {request.prompt_len} > "
                f"max_prompt_len {self.max_prompt_len}")
        if request.deadline_s is not None and request.deadline_s <= 0:
            raise ValueError(
                f"request {request.rid}: deadline_s must be positive")
        if self.cfg.family == "encdec" and request.frontend_embeds is None:
            # without frames the cross-KV stays all-zero: the request would
            # "succeed" while conditioning on a null encoder
            raise ValueError(
                f"request {request.rid}: encdec family needs "
                f"frontend_embeds")
        now = self._clock()
        request.t_submit = now
        tr = self._tracer
        if tr is not None:
            tr.req_phase(request.rid, "queued")
        # degradation ladder, last rung: the admission queue is bounded
        # and the lowest-priority request (newest on ties) is shed
        if (self._levels[self._level] == "shed"
                and len(self.scheduler.queue) >= self.queue_bound):
            victim = min(
                [request] + list(self.scheduler.queue),
                key=lambda r: (r.priority,
                               -(r.seq if r.seq is not None else 1 << 62)))
            if victim is not request:
                self.scheduler.queue.remove(victim)
            victim.status = RequestStatus.FINISHED
            victim.finish_reason = "rejected"
            victim.t_finish = now
            self.stats["rejected"] += 1
            self.stats["finished"] += 1
            if tr is not None:
                tr.req_terminal(victim.rid, "rejected",
                                shed_for=request.rid)
            if victim is request:
                return
        self.scheduler.submit(request)

    # -- tick loop --------------------------------------------------------

    def _release_backoff(self) -> None:
        """Re-enter preempted requests whose backoff has elapsed."""
        if not self._backoff:
            return
        ready = [r for t, r in self._backoff if t <= self._tick_no]
        self._backoff = [(t, r) for t, r in self._backoff
                         if t > self._tick_no]
        for req in ready:
            self.scheduler.submit(req)
            if self._tracer is not None:
                self._tracer.req_phase(req.rid, "queued", requeue=True)

    def _admit_pass(self) -> None:
        if self.paged:
            # one at a time: each admission's block allocation must be
            # visible to the next can_admit capacity check
            while True:
                admitted = self.scheduler.admit(limit=1)
                if not admitted:
                    break
                self._admit(*admitted[0])
        else:
            for slot, req in self.scheduler.admit():
                self._admit(slot, req)

    def _admit_and_map(self) -> None:
        """Backoff release + admission + deadline preemption + (paged)
        mapping of this tick's write window."""
        ann = self._prof.annotate
        with ann("engine.admit"):
            self._release_backoff()
            self._admit_pass()
            if self._deadline_preempt(self._clock()):
                self._admit_pass()
        if self.paged:
            with ann("engine.map"):
                self._ensure_blocks(need=(self.spec_k_eff or 0) + 1)

    def tick(self) -> int:
        """Deadline sweep + admit + one fused decode step; returns
        #active slots advanced.  Every piece of the tick runs in a named
        ``Prof`` phase (``obs/prof.py``)."""
        tick_no = self._tick_no
        self._tick_no += 1
        if self._obs_tick is not None:    # exporter cadence + profile
            self._obs_tick(tick_no)       # window; None when neither set
        ann = self._prof.annotate
        # opened after the hook: a profile window that starts here still
        # records this tick's span
        with ann("engine.tick"):
            with ann("engine.expire"):
                self._expire_deadlines(self._clock())
            t0 = self._timer()
            if self.spec_k_eff:
                n = self._tick_spec(tick_no)
            else:
                n = self._tick_decode(tick_no)
            with ann("engine.pressure"):
                dt = self._timer() - t0
                if self._fault is not None:
                    extra = self._fault.extra_tick_s(tick_no)
                    if extra and self._tracer is not None:
                        self._tracer.instant("engine", "fault:slow_tick",
                                             tick=tick_no, extra_s=extra)
                    dt += extra
                self._h_tick.observe(dt)
                self._observe_pressure(dt, tick_no)
        return n

    def _tick_decode(self, tick_no: int) -> int:
        self._admit_and_map()
        active = self.scheduler.active()
        if not active:
            return 0
        ann = self._prof.annotate
        with ann("engine.rng"):
            rng = self._decode_rng(self.stats["decode_ticks"])
        t0 = self._timer()
        with ann("decode"):
            with ann("decode.inputs"):
                if self.paged:
                    pos = self._positions.copy()
                    for slot in self._stalled:
                        pos[slot] = self._park  # no write/token this tick
                    args = (jnp.asarray(self._tokens), jnp.asarray(pos),
                            jnp.asarray(self.allocator.table))
                else:
                    args = (jnp.asarray(self._tokens),
                            jnp.asarray(self._positions))
            with ann("decode.launch"):
                tok, self._cache = self._decode(self.params, self._cache,
                                                *args, rng)
            with ann("decode.wait"):
                tok_np = np.asarray(tok)
        with ann("engine.commit"):
            self._commit_decode(active, tok_np, tick_no, t0)
        return len(active)

    def _commit_decode(self, active, tok_np: np.ndarray, tick_no: int,
                       t0: float) -> None:
        """Append each live slot's sampled token and retire the slots
        that finished."""
        self.stats["decode_s"] += self._timer() - t0
        self.stats["decode_ticks"] += 1
        self.stats["stalled_slot_ticks"] += len(self._stalled)
        if self._fault is not None and self._fault.logits_corrupt(
                tick_no):
            # simulated NaN/inf logits: every sampled id is garbage
            tok_np = np.full_like(tok_np, -1)
            self.stats["corrupt_ticks"] += 1
            if self._tracer is not None:
                self._tracer.instant("engine", "fault:corrupt_logits",
                                     tick=tick_no)
        now = self._clock()
        for slot, req in active:
            if slot in self._stalled:
                continue  # parked this tick: its sampled token is junk
            t = int(tok_np[slot])
            if not 0 <= t < self.cfg.vocab_size:
                # corrupt decode output: heal by recompute — requeue
                # and re-prefill rather than commit a garbage token
                self._heal_or_kill(slot, req, now)
                continue
            req.generated.append(t)
            self.stats["tokens_out"] += 1
            self._positions[slot] += 1
            self._tokens[slot] = t
            self._maybe_finish(slot, req, t, now)

    def _tick_spec(self, tick_no: int) -> int:
        """One speculative tick: draft k, verify once, advance each slot
        by its accepted length, roll back the rest."""
        k = self.spec_k_eff
        self._admit_and_map()
        active = self.scheduler.active()
        if not active:
            return 0
        ann = self._prof.annotate
        with ann("engine.rng"):
            tick_rng = self._decode_rng(self.stats["decode_ticks"])
            draft_rng = jax.random.fold_in(tick_rng, 0)
            verify_rng = jax.random.fold_in(tick_rng, 1)
        pos = self._positions.copy()
        for slot in self._stalled:
            pos[slot] = self._park  # no writes, no tokens this tick

        t0 = self._timer()
        with ann("draft"):
            drafts, draft_logits = self.draft.propose(self._tokens, pos,
                                                      draft_rng)
        tok_mat = np.concatenate([self._tokens[:, None], drafts],
                                 axis=1).astype(np.int32)
        with ann("verify"):
            with ann("verify.inputs"):
                args = (jnp.asarray(tok_mat), jnp.asarray(drafts),
                        draft_logits, jnp.asarray(pos))
                if self.paged:
                    args += (jnp.asarray(self.allocator.table),)
            with ann("verify.launch"):
                acc, out, self._cache = self._verify(
                    self.params, self._cache, *args, verify_rng)
            with ann("verify.wait"):
                acc_np = np.asarray(acc)
                out_np = np.asarray(out)
        with ann("engine.commit"):
            self._commit_spec(active, k, acc_np, out_np, tick_no, t0)
        return len(active)

    def _commit_spec(self, active, k: int, acc_np: np.ndarray,
                     out_np: np.ndarray, tick_no: int, t0: float) -> None:
        """Advance each slot by its accepted length and roll back the
        rest of its verify window."""
        self.stats["decode_s"] += self._timer() - t0
        self.stats["decode_ticks"] += 1
        self.stats["stalled_slot_ticks"] += len(self._stalled)
        corrupt = (self._fault is not None
                   and self._fault.logits_corrupt(tick_no))
        if corrupt:
            self.stats["corrupt_ticks"] += 1
            if self._tracer is not None:
                self._tracer.instant("engine", "fault:corrupt_logits",
                                     tick=tick_no)

        now = self._clock()
        n_adv = np.zeros((self.n_slots,), np.int32)
        for slot, req in active:
            if slot in self._stalled:
                continue
            if corrupt:
                # simulated NaN/inf verify logits: commit nothing for the
                # slot, heal by recompute (requeue -> re-prefill)
                self._heal_or_kill(slot, req, now)
                continue
            n = int(acc_np[slot])
            self.stats["drafted"] += k
            self.stats["accepted"] += n
            # commit the accepted drafts plus the correction/bonus token,
            # applying the per-token stop rules in stream order so EOS /
            # budget / ceiling cut the stream exactly where the
            # non-speculative engine would
            for i in range(n + 1):
                t = int(out_np[slot, i])
                if not 0 <= t < self.cfg.vocab_size:
                    self._heal_or_kill(slot, req, now)
                    break
                req.generated.append(t)
                self.stats["tokens_out"] += 1
                self._positions[slot] += 1
                self._tokens[slot] = t
                n_adv[slot] += 1
                self._maybe_finish(slot, req, t, now)
                if req.done:
                    break
        # (acceptance_rate needs no update here: it is a derived gauge
        # over the drafted/accepted counters, computed at read time)
        self.draft.commit(n_adv)
        if self.paged:
            # rollback: return verify-window pages beyond each surviving
            # slot's committed frontier (finished slots already freed all,
            # preempted/healed slots were fully released by the requeue).
            # +1 keeps the page the NEXT tick writes first: releasing it on
            # a page-boundary frontier would let the admission pass snatch
            # it back and spuriously stall (or even preempt) this slot.
            for slot, req in active:
                if (req.status is RequestStatus.ACTIVE
                        and slot not in self._stalled):
                    self.allocator.trim_slot(
                        slot, int(self._positions[slot]) + 1)

    @property
    def has_work(self) -> bool:
        """Queued, active, or backoff-parked work remains."""
        return self.scheduler.has_work or bool(self._backoff)

    def run(self, requests: Sequence[Request],
            max_ticks: Optional[int] = None,
            wall_clock_limit_s: Optional[float] = None) -> List[Request]:
        """Submit everything, tick until drained, return the requests.

        ``wall_clock_limit_s`` bounds the real time spent in the loop: a
        hung or livelocked tick loop (e.g. a fault plan that never lets a
        page map) exits with partial results — ``wall_clock_exceeded`` set
        and unfinished requests left in their current state — instead of
        spinning forever.  ``max_ticks`` still bounds the tick count
        exactly and raises, as a logic-error (not overload) guard.
        """
        for r in requests:
            self.submit(r)
        ticks = 0
        t0 = time.perf_counter()
        while self.has_work:
            if (wall_clock_limit_s is not None
                    and time.perf_counter() - t0 > wall_clock_limit_s):
                self.wall_clock_exceeded = True
                break
            if max_ticks is not None and ticks >= max_ticks:
                raise RuntimeError(f"engine not drained after {ticks} ticks")
            self.tick()
            ticks += 1
        return list(requests)

    # -- deadlines / preemption -------------------------------------------

    def _expire_deadlines(self, now: float) -> None:
        """Sweep queued and active requests past their deadline to
        ``finish_reason="timeout"``."""
        for req in self.scheduler.expire(now):
            req.status = RequestStatus.FINISHED
            req.finish_reason = "timeout"
            req.t_finish = now
            self.stats["timeout"] += 1
            self.stats["finished"] += 1
            if self._tracer is not None:
                self._tracer.req_terminal(req.rid, "timeout", queued=True)
        for slot, req in self.scheduler.active():
            if now >= req.deadline_abs():
                self.stats["timeout"] += 1
                self._finish(slot, req, "timeout", now)

    def _can_requeue(self, req: Request) -> bool:
        """May this active request be preempted-with-requeue?  Needs
        budget left and a context short enough to re-prefill (the prompt
        plus generated-so-far must fit the prefill window)."""
        return (req.n_preemptions < req.max_preemptions
                and req.ctx_len <= self.max_prompt_len)

    def _evict_reason(self, req: Request) -> str:
        return ("preempted_limit"
                if req.n_preemptions >= req.max_preemptions
                else "cache_full")

    def _preempt(self, slot: int, req: Request) -> None:
        """Preempt-and-requeue with recompute: release the slot (and its
        pages), park the row, and send the request back to the queue with
        exponential tick backoff.  Its generated-so-far tokens stay on the
        request and fold into the re-prefill context at readmission, so a
        greedy stream continues bit-identically."""
        req.n_preemptions += 1
        self.scheduler.release(slot)
        if self.paged:
            self.allocator.free_slot(slot)
        self._positions[slot] = self._park      # park: no cache writes
        self._stalled.discard(slot)
        req.status = RequestStatus.QUEUED
        self.stats["preempted"] += 1
        self.stats["requeued"] += 1
        backoff = 1 << min(req.n_preemptions - 1, 6)
        self._backoff.append((self._tick_no + backoff, req))
        if self._tracer is not None:
            self._tracer.req_instant(req.rid, "preempt", slot=slot,
                                     n_preemptions=req.n_preemptions)
            self._tracer.req_phase(req.rid, "backoff", ticks=backoff)

    def preempt(self, slot: int) -> None:
        """Public preempt-and-requeue of the request in ``slot`` — the
        building block a multi-replica front door's drain-and-redistribute
        uses, and the deterministic hook the resilience tests drive."""
        req = self.scheduler.slots[slot]
        if req is None:
            raise ValueError(f"slot {slot} is free")
        if not self._can_requeue(req):
            raise ValueError(
                f"request {req.rid} cannot requeue (preemptions "
                f"{req.n_preemptions}/{req.max_preemptions}, ctx "
                f"{req.ctx_len} vs max_prompt_len {self.max_prompt_len})")
        self._preempt(slot, req)

    def _heal_or_kill(self, slot: int, req: Request, now: float) -> None:
        """Corrupt decode output for this slot: requeue-with-recompute if
        the budget allows, terminal eviction otherwise."""
        if self._can_requeue(req):
            self._preempt(slot, req)
        else:
            self.stats["preempted"] += 1
            self._finish(slot, req, self._evict_reason(req), now)

    def _deadline_preempt(self, now: float) -> bool:
        """A queued request about to miss its deadline may evict-with-
        requeue the active request with the most slack.  At most one
        preemption per tick; the victim must itself be requeueable and
        strictly less urgent than the starving request."""
        starving = self.scheduler.most_urgent()
        if starving is None or starving.deadline_s is None:
            return False
        slack = starving.slack(now)
        if slack > self.deadline_margin_s:
            return False
        cands = [(s, r) for s, r in self.scheduler.active()
                 if self._can_requeue(r) and r.slack(now) > slack]
        if not cands:
            return False
        slot, req = max(
            cands,
            key=lambda sr: (sr[1].slack(now), -sr[1].priority,
                            self.allocator.blocks_held(sr[0])
                            if self.paged else 0))
        self.stats["deadline_preempts"] += 1
        if self._tracer is not None:
            self._tracer.instant("engine", "deadline_preempt",
                                 victim=req.rid, starving=starving.rid)
        self._preempt(slot, req)
        return True

    # -- degradation ladder ------------------------------------------------

    @property
    def degrade_level(self) -> str:
        """Current ladder rung name (``full`` when healthy)."""
        return self._levels[self._level]

    def _observe_pressure(self, dt: float, tick_no: int) -> None:
        """Feed the tick-latency watchdog and pool/queue pressure signals;
        step the ladder down after ``degrade_down_after`` consecutive hot
        ticks, back up after ``degrade_up_after`` consecutive calm ones."""
        straggler = self._watchdog.observe(tick_no, dt)
        if straggler and self._tracer is not None:
            self._tracer.instant("engine", "straggler", tick=tick_no,
                                 dt_s=dt)
        pool_dry = (self.paged and bool(self._stalled)
                    and self.allocator.n_free == 0)
        queue_over = len(self.scheduler.queue) > self.queue_bound
        if straggler or pool_dry or queue_over:
            self._hot += 1
            self._calm = 0
            if (self._hot >= self.degrade_down_after
                    and self._level < len(self._levels) - 1):
                self._set_level(self._level + 1)
                self._hot = 0
        else:
            self._calm += 1
            self._hot = 0
            if self._calm >= self.degrade_up_after and self._level > 0:
                self._set_level(self._level - 1)
                self._calm = 0

    def _set_level(self, level: int) -> None:
        """Apply one reversible ladder transition.  Ordering guarantee:
        levels only ever change speculation depth (token streams are
        invariant — greedy speculation is exact at any k, including 0)
        or gate NEW admissions (shedding); tokens already streaming are
        never altered by a transition."""
        if level > self._level:
            self.stats["degrade_down"] += 1
        else:
            self.stats["degrade_up"] += 1
        if self._tracer is not None:
            self._tracer.instant(
                "engine", "ladder",
                src=self._levels[self._level], dst=self._levels[level],
                direction="down" if level > self._level else "up")
        self._level = level
        self.stats["degrade_level"] = level
        name = self._levels[level]
        k_eff = {"full": self.spec_k,
                 "spec_half": max(1, self.spec_k // 2),
                 "spec_off": 0,
                 "shed": 0}[name]
        if self.spec_k and k_eff != self.spec_k_eff:
            self.spec_k_eff = k_eff
            if k_eff and self.draft is not None:
                self.draft.set_k(k_eff)
        # the per-tick cost legitimately changed with the level: re-seed
        # the watchdog baseline instead of flagging every healthy tick
        self._watchdog.reset()

    # -- internals --------------------------------------------------------

    def _admit(self, slot: int, req: Request) -> None:
        # re-prefill context: the prompt plus (after a preemption) every
        # token generated so far — recompute makes the requeue transparent
        ctx = list(req.prompt) + [int(t) for t in req.generated]
        clen = len(ctx)
        p = self.max_prompt_len
        toks = np.zeros((1, p), np.int32)
        toks[0, :clen] = np.asarray(ctx, np.int32)
        lengths = jnp.asarray([clen], jnp.int32)
        fe = getattr(req, "frontend_embeds", None)
        if self._tracer is not None:
            self._tracer.req_phase(req.rid, "prefill", slot=slot,
                                   ctx_len=clen)
        t0 = self._timer()
        ann = self._prof.annotate
        with ann("prefill"):
            with ann("prefill.inputs"):
                if self.paged:
                    self.allocator.alloc_slot(slot, clen)
                    args = (self._slot_template, jnp.asarray(toks), lengths,
                            jnp.asarray(self.allocator.phys_row(slot)),
                            jnp.int32(slot), fe)
                else:
                    args = (self._slot_template, jnp.asarray(toks), lengths,
                            fe)
            with ann("prefill.launch"):
                if self.paged:
                    last_logits, self._cache = self._prefill(
                        self.params, self._cache, *args)
                else:
                    last_logits, slot_cache = self._prefill(self.params,
                                                            *args)
                    self._cache = self._insert(self._cache, slot_cache,
                                               jnp.int32(slot))
            with ann("prefill.sample"):
                tok = int(self._sample(self._admit_rng(req.rid),
                                       last_logits)[0])
            if self.draft is not None:
                # the draft mirrors the slot layout: its own (cheap)
                # prefill fills its cache row so drafting starts from the
                # same prompt
                self.draft.prefill(slot, jnp.asarray(toks), lengths, fe)
        self.stats["prefill_s"] += self._timer() - t0
        self.stats["prefill_dispatches"] += 1
        now = self._clock()
        if req.t_first_token is None:       # readmissions keep the mark
            req.t_first_token = now
            if req.t_submit is not None:
                self._h_ttft.observe(now - req.t_submit)
        if self._tracer is not None:
            self._tracer.req_phase(req.rid, "decode", slot=slot)
        req.generated.append(tok)
        self.stats["tokens_out"] += 1
        self._tokens[slot] = tok
        self._positions[slot] = clen
        self._maybe_finish(slot, req, tok, now)

    def _ensure_blocks(self, need: int = 1) -> None:
        """Map each active slot's write window (``need`` positions from its
        frontier — 1 per decode tick, k+1 per speculative tick); stall
        slots the pool cannot serve, and break an all-stalled deadlock by
        preempting-with-requeue the lowest-priority stalled request
        holding the most pages (terminal eviction only when its requeue
        budget or re-prefill window is exhausted)."""
        self._stalled = set()
        active = self.scheduler.active()
        for slot, _ in active:
            forced = (self._fault is not None
                      and self._fault.spurious_stall(slot))
            if forced and self._tracer is not None:
                self._tracer.instant("engine", "fault:spurious_stall",
                                     slot=slot)
            if forced or not self.allocator.ensure_range(
                    slot, int(self._positions[slot]), need):
                self._stalled.add(slot)
        if self._stalled and len(self._stalled) == len(active):
            stalled = [(s, r) for s, r in active if s in self._stalled]
            requeueable = [(s, r) for s, r in stalled
                           if self._can_requeue(r)]
            pool = requeueable or stalled
            slot, req = max(pool, key=lambda sr: (
                -sr[1].priority, self.allocator.blocks_held(sr[0])))
            if requeueable:
                self._preempt(slot, req)
            else:
                self.stats["preempted"] += 1
                self._finish(slot, req, self._evict_reason(req),
                             self._clock())
                self._stalled.discard(slot)
            for slot2 in sorted(self._stalled):
                if self.allocator.ensure_range(
                        slot2, int(self._positions[slot2]), need):
                    self._stalled.discard(slot2)

    def _maybe_finish(self, slot: int, req: Request, last_token: int,
                      now: float) -> None:
        reason = None
        if req.eos_id is not None and last_token == req.eos_id:
            reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            reason = "length"
        elif self._positions[slot] >= self.max_len:
            reason = "cache_full"   # no room to write the next token
        if reason is None:
            return
        self._finish(slot, req, reason, now)

    def _finish(self, slot: int, req: Request, reason: str,
                now: float) -> None:
        req.status = RequestStatus.FINISHED
        req.finish_reason = reason
        req.t_finish = now
        self.scheduler.release(slot)
        if self.paged:
            self.allocator.free_slot(slot)
        self._positions[slot] = self._park      # park: no cache writes
        self.stats["finished"] += 1
        n = len(req.generated)
        if req.t_first_token is not None and n > 1:
            self._h_tpot.observe(
                max(now - req.t_first_token, 0.0) / (n - 1))
        if self._tracer is not None:
            self._tracer.req_terminal(req.rid, reason, tokens=n)
