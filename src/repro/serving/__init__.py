"""Serving engine: batched prefill + continuous batching over the model zoo.

Why this package exists: ACDC's pitch is cheap inference — O(N) parameters,
O(N log N) operations per structured projection — and the serving layer is
where that cost advantage is actually cashed in.  This package turns the
model zoo's decode machinery (KV caches in ``repro/models/attention.py``,
SSM/conv state in ``repro/models/mamba2.py``) into an engine.

Request lifecycle
-----------------
A :class:`Request` (``request.py``) carries a ragged-length prompt, its
stop conditions (``eos_id``, ``max_new_tokens``), and its scheduling
inputs (``deadline_s``, ``priority``, ``max_preemptions``).
``Engine.submit`` validates it and hands it to the deadline-aware
:class:`Scheduler` (``scheduler.py``) as QUEUED.  When a batch slot frees
up it becomes ACTIVE: one lowered **prefill** program
(``make_prefill_step``) runs the whole context, scatters the resulting
KV / SSM state into the slot's cache row, and samples the next token —
the time-to-first-token mark on first admission.  Each subsequent engine
tick advances it one token; a terminal condition flips it to FINISHED
and releases the slot.  ``finish_reason`` is one of the closed
:class:`FinishReason` set:

* ``"eos"`` — generated the request's ``eos_id``;
* ``"length"`` — generated ``max_new_tokens`` tokens;
* ``"cache_full"`` — hit the per-slot ``max_len`` cache ceiling, or was
  terminally evicted while its context was too long to re-prefill;
* ``"timeout"`` — passed ``t_submit + deadline_s`` (queued or active);
* ``"preempted_limit"`` — needed another preemption after exhausting its
  ``max_preemptions`` requeue budget;
* ``"rejected"`` — shed at submission by the degradation ladder's
  bounded queue (overload; lowest priority goes first).

Preempt -> requeue -> re-prefill
--------------------------------
Preemption is the engine's universal recovery move: the victim slot is
released (pages returned to the pool), the request moves ACTIVE ->
QUEUED with its ``generated`` tokens kept, and after an exponential
tick backoff (``2^(n_preemptions - 1)`` ticks, capped at 64) it
re-enters the queue with its original arrival ``seq`` — seniority and
deadline urgency are unchanged.  Readmission re-prefills the whole
context ``prompt + generated`` and samples the next token from the
last-position logits; because prefill and decode agree
position-for-position (pinned by tests/test_decode_consistency.py), a
greedy stream continues **bit-identically** to an undisturbed run —
recompute makes preemption transparent, trading only latency.  The same
state machine serves four callers: the all-stalled deadlock breaker
(pool exhausted), deadline preemption (a queued request about to miss
its deadline evicts the active request with the most slack), corrupt-
output healing (non-finite logits -> out-of-range sampled ids ->
requeue instead of committing garbage), and the public
``Engine.preempt(slot)`` hook (a multi-replica front door's
drain-and-redistribute building block).  A request that cannot requeue
(budget spent, or ``prompt + generated`` no longer fits
``max_prompt_len``) is finished terminally instead
(``preempted_limit`` / ``cache_full``).

Deadline-aware scheduling
-------------------------
Admission order is earliest-deadline-first: queued requests sort by
absolute deadline (no deadline sorts last), then priority, then arrival
— exactly FIFO when no deadlines or priorities are set.  Each tick
sweeps queued requests already past their deadline to ``timeout``
without burning a prefill, and evicts active ones on expiry.  A
capacity-blocked queue head is aged (``scheduler.py``): after
``age_limit`` skipped passes the scheduler admits nobody else, so freed
capacity accrues until the head fits — bounding head-of-line starvation
that the bounded lookahead ``window`` alone could sustain forever.

Graceful-degradation ladder
---------------------------
A tick-latency watchdog (:class:`repro.dist.elastic.StragglerMonitor`)
plus pool-pressure (all slots stalled on a dry pool) and queue-depth
signals drive a reversible ladder: ``full -> spec_half -> spec_off ->
shed`` (speculation rungs exist only when ``spec_k`` allows).  Each
step down shrinks speculative depth, then disables speculation, then
bounds the admission queue at ``queue_bound`` and sheds the lowest-
priority request (``finish_reason="rejected"``).  Ordering guarantees:
rungs are strictly ordered cheapest-first; transitions are counted in
``stats["degrade_down"/"degrade_up"/"degrade_level"]``; and **no
transition ever alters a greedy token stream** — speculation is exact
at any depth (including 0) and shedding only drops whole requests at
submission, never tokens from streaming ones.  After
``degrade_up_after`` consecutive calm ticks the engine steps back up;
the watchdog baseline resets on every transition because the per-tick
cost legitimately changed.

Fault injection
---------------
``Engine(..., fault=FaultPlan(seed=...))`` (``faults.py``) threads a
deterministic seed-driven chaos schedule behind a no-op default into
the allocator (capacity checks / page mapping report a dry pool) and
the tick loop (non-finite logits on chosen ticks, simulated slow ticks
for the watchdog, spurious slot stalls).  Each fault surface draws from
its own seeded stream, so plans replay exactly;
``BlockAllocator.audit()`` must come back clean after any plan
(tests/test_serving_faults.py replays seeded chaos and asserts every
request reaches a terminal state with unpreempted streams
bit-identical).

Slot model
----------
The :class:`Engine` (``engine.py``) owns a fixed-shape cache with
``n_slots`` batch rows (max_len positions each).  Prefill writes a slot's
entire row — positions at or beyond the prompt length are zeroed, because
the decode path scatters additively — so slots are reused without a reset
pass.  Free slots ride through decode parked at ``position = max_len``,
where the one-hot scatter writes nothing.  Per-request compute is
batch-row-independent, so outputs are identical to running each request
alone (pinned by tests/test_serving_engine.py).

Paged block KV cache
--------------------
``Engine(..., paged=True, block_size=B, n_blocks=N)`` replaces the dense
per-slot ``max_len`` slabs with ONE global pool of ``N`` pages of ``B``
token positions each (``blocks.py``), so short requests stop paying a long
request's worst-case memory.  The device layout (shared with
``repro.models.attention``):

* page pool ``(n_layers, N + 1, B, Hkv, Dh)`` per K and V — physical page
  ``N`` is the write sink for parked/stalled rows, never read back;
* block table: static ``(n_slots, ceil(max_len / B))`` int32, entry
  ``[slot, i]`` = physical page for token positions ``[i*B, (i+1)*B)``,
  ``-1`` when unmapped.  The host-side :class:`BlockAllocator` owns it and
  the engine ships it to the device each tick.

The table carries two invariants the attention consumers rely on:

* **frontier** — for any slot the engine decodes at ``position = p <
  virtual`` (virtual = ``ceil(max_len / B) * B``), every entry covering
  ``[0, p]`` is mapped: ``_ensure_blocks`` maps the tick's whole write
  window up front and *parks* (stalls) any slot it cannot serve at
  ``position = virtual``.  Unmapped entries therefore only ever sit
  ABOVE a live slot's frontier.
* **masking** — readers must derive their key mask from ``position``
  alone, never from table occupancy: pages are recycled across requests
  (evict -> admit remaps them to other slots mid-stream), so a freed
  page holds stale K/V that only the causal/frontier mask keeps out of
  attention (pinned by tests/test_paged_attention.py).

Two interchangeable attention consumers honour that contract
(``ops.paged_attn_route`` picks per trace, counting decisions in
``PAGED_ATTN_DISPATCHES``): the block-table *gather* in
``models/attention.py`` — materialises the ``(n_slots, virtual, Hkv,
Dh)`` view, routing unmapped entries through page 0 (masked anyway) —
and the fused Pallas kernel in ``kernels/paged_attn.py``, which streams
only the mapped in-frontier pages (O(len) bytes per slot instead of the
gather's O(max_len)) and is the TPU default whenever an autotuned block
fits VMEM; the gather stays as the over-budget/interpret fallback.
Greedy streams are bit-identical either way.

Admission contract: the queue head is admitted only when
``ceil((prompt_len + 1) / B)`` pages are free — prompt plus room for the
first decode token — so admission never strands a request with nowhere to
write.  Decode growth maps pages lazily each tick; a slot the pool cannot
serve *stalls* (parks for the tick, produces nothing, resumes when an
eviction frees pages), and an all-stalled deadlock is broken by
preempting-with-requeue the lowest-priority stalled request holding the
most pages (see the state machine above).  Because slots are compute-
isolated, greedy output streams under paging are identical to the dense
cache (pinned by tests/test_serving_paged.py); only scheduling/latency
can shift when the pool is tight.  Families: transformer and encdec page
their (self-attention) KV, zamba2 pages only the shared-attention KV
(Mamba SSM/conv state is O(1) per slot and stays dense), mamba2 has
nothing to page by construction.

Admission under paging uses a bounded head-of-line lookahead (scheduler
``window``, default 4): when the queue head's prompt does not fit the
free pool, the first of the next ``window`` queued requests that does is
admitted instead — the head stays at the front and is retried every
pass, so one large request cannot starve a stream of small ones.

Tick loop
---------
``tick()`` = admit (0+ prefill dispatches, one per admission) + one fused
decode step over all ``n_slots`` rows + evict.  All shapes are static, so
the engine compiles exactly two programs — one prefill, one decode — no
matter how traffic arrives (paged mode fuses the admission page scatter
into the prefill program, keeping the count at two).  ``run(requests)``
ticks until drained, raising once ``max_ticks`` ticks have run without
draining.

Speculative tick (``spec_k > 0``)
---------------------------------
The decode step is replaced by **draft -> verify -> accept/rollback**
(:mod:`repro.spec`):

1. *draft* — one fused program proposes ``k`` tokens per slot from a
   cheap draft source (default: the target's own ACDC cascades truncated
   to ``draft_depth`` layers, the paper's depth result as a free draft);
2. *verify* — ONE target program (``make_verify_step``) appends all
   ``k + 1`` tokens per slot (pending + drafts) to the cache as a
   position-masked mini-prefill, scores every position, accepts the
   longest draft prefix the target agrees with, and commits;
3. *accept/rollback* — each slot advances by its accepted length plus
   one correction/bonus token (variable per slot; shapes stay static,
   parked rows just write to nowhere).  Rejected tail positions roll
   back: KV caches are SET-written by the verify scatter, so a rewind of
   ``positions`` suffices (the stale tail sits beyond the causal mask
   and the next set-write overwrites it exactly); paged caches also
   return over-mapped tail pages to the allocator
   (``BlockAllocator.trim_slot``); recurrent SSM/conv state cannot
   rewind and is re-committed from per-position snapshots instead.

Invariants: a draft token is accepted under greedy sampling iff it
equals the target argmax at its position, and the verify logits are
computed by the same per-position reductions as the decode step — so
greedy speculative streams are **bit-identical** to the non-speculative
engine no matter how bad the draft is (the draft only moves the
acceptance rate, i.e. how many target dispatches each token costs).
Temperature sampling uses standard rejection sampling, which preserves
the target distribution exactly.  ``stats["drafted"/"accepted"/
"acceptance_rate"]`` track draft quality.

Sampling (``sampler.py``) is shared between the fused decode step and the
admission path: greedy, or temperature with top-k / top-p filtering.
Decode ticks and admissions draw from disjoint chained ``fold_in``
streams, so tick counters and request ids can never collide.

Stats keys <-> registry metrics
-------------------------------
``Engine.stats`` keeps its historical flat-dict surface but is a
:class:`repro.obs.metrics.StatsView` over a per-engine metric registry
(``Engine(..., obs=Observability(...))``; the authoritative key->metric
table is ``repro.serving.engine.STATS_METRICS``).  Every key below reads
(and, except where noted, writes) the registry metric on the right:

===================  ====================================  =============
stats key            registry metric                       kind
===================  ====================================  =============
prefill_dispatches   serve_prefill_dispatches_total        counter
decode_ticks         serve_decode_ticks_total              counter
tokens_out           serve_tokens_out_total                counter
finished             serve_finished_total                  counter
preempted            serve_preempted_total                 counter
requeued             serve_requeued_total                  counter
timeout              serve_timeout_total                   counter
rejected             serve_rejected_total                  counter
deadline_preempts    serve_deadline_preempts_total         counter
corrupt_ticks        serve_corrupt_ticks_total             counter
stalled_slot_ticks   serve_stalled_slot_ticks_total        counter
degrade_down         serve_degrade_down_total              counter
degrade_up           serve_degrade_up_total                counter
degrade_level        serve_degrade_level                   gauge
prefill_s            serve_prefill_seconds_total           counter
decode_s             serve_decode_seconds_total            counter
drafted              serve_spec_drafted_total              counter
accepted             serve_spec_accepted_total             counter
acceptance_rate      serve_acceptance_rate                 derived gauge
                                                           (READ-ONLY:
                                                           accepted /
                                                           drafted at
                                                           read time)
===================  ====================================  =============

Latency histograms (``serve_ttft_seconds``, ``serve_tpot_seconds``,
``serve_tick_seconds``) have no stats key — read them off the engine's
registry (``obs.registry.get(name)``); the overload bench reports its
percentiles from them.  The full metric glossary, including the
process-global kernel/autotune/training names, lives in
``repro/obs/__init__.py``.
"""

from repro.dist.steps import (  # noqa: F401
    make_prefill_step,
    make_serve_step,
    make_verify_step,
)
from repro.serving.blocks import BlockAllocator  # noqa: F401
from repro.serving.engine import Engine  # noqa: F401
from repro.serving.faults import FaultPlan  # noqa: F401
from repro.serving.request import (  # noqa: F401
    FinishReason,
    Request,
    RequestStatus,
)
from repro.serving.sampler import (  # noqa: F401
    apply_top_k,
    apply_top_p,
    sample,
)
from repro.serving.scheduler import Scheduler  # noqa: F401
