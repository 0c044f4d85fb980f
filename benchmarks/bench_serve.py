"""Serving-path benchmark: sequential-decode prefill vs batched prefill vs
continuous batching vs the paged block KV cache.

    PYTHONPATH=src python -m benchmarks.bench_serve [--arch qwen3_1_7b]
        [--slots 4] [--prompt-len 32] [--gen 32] [--requests 12]
        [--block-size 16]

Four modes over the same smoke-scale model and workload:

* ``sequential``  — the pre-engine serving path: the prompt is fed one
  token at a time through the fused decode step (``prompt_len`` dispatches
  per request), then greedy decode;
* ``batched_prefill`` — ONE lowered prefill program per batch ingests all
  prompts, then lockstep greedy decode (static batching);
* ``continuous``  — the slot engine: per-admission prefill (one dispatch
  per request), one fused decode tick for all active slots, eviction +
  refill under a Poisson-ish ragged arrival stream;
* ``paged``       — the same engine and workload on the paged block KV
  cache, with the pool sized from the mix's actual demand (top
  ``n_slots`` per-request page needs) instead of ``n_slots * max_len``;
* ``paged_fused`` — the paged run again with the fused streaming
  paged-attention kernel forced (``kernels/paged_attn.py``) instead of
  the block-table gather, asserting token-identical streams and that the
  dispatch counters recorded only fused decisions.

Wall-clock for ``paged_fused`` is reported but tagged ``non_roofline``
off-TPU, where the kernel runs interpreted.

``--spec`` adds an A/B pair on an ACDC SELL smoke model: ``spec_baseline``
(the plain continuous engine) vs ``spec`` (truncated-cascade self-draft +
batched k-token verify), asserting token-identical greedy streams with
strictly fewer target-model dispatches per generated token, and reporting
the measured draft acceptance rate.

Accounting is comparable across modes: ``decode_tok_per_s`` is always
decode-step tokens over decode-step time (the engine modes exclude the
per-request prefill-sampled first token and the prefill dispatch time —
mixing them in made continuous look ~5x slower than sequential);
``total_s`` keeps the end-to-end view.  Emits ``results/BENCH_serve.json``
with two acceptance checks: engine modes issue ONE lowered prefill program
per admission, and the paged pool holds strictly fewer cache bytes than
the dense slabs while emitting identical greedy token streams.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import timing_meta
from repro.configs import registry
from repro.dist import steps as steps_mod
from repro.kernels import ops
from repro.kernels import paged_attn
from repro.models import get_model
from repro.obs import Observability, SpanTracer, set_global_tracer
from repro.serving import Engine, Request
from repro.serving.request import make_ragged_requests

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def bench_sequential(model, cfg, params, prompts, gen: int):
    """Old serving path: prompt tokens through the decode step one by one."""
    b, p = prompts.shape
    serve = jax.jit(steps_mod.make_serve_step(model, cfg))
    cache = model.init_cache(cfg, b, p + gen + 1)
    rng = jax.random.PRNGKey(0)
    # warmup compile outside the timed region
    serve(params, model.init_cache(cfg, b, p + gen + 1), prompts[:, 0],
          jnp.zeros((b,), jnp.int32), rng)[0].block_until_ready()

    t0 = time.perf_counter()
    tok = prompts[:, 0]
    dispatches = 0
    for i in range(p - 1):
        _, cache = serve(params, cache, tok, jnp.full((b,), i, jnp.int32),
                         rng)
        tok = prompts[:, i + 1]
        dispatches += 1
    nxt, cache = serve(params, cache, tok, jnp.full((b,), p - 1, jnp.int32),
                       rng)
    dispatches += 1
    jax.block_until_ready(nxt)
    t_first = time.perf_counter() - t0          # ttft: whole prompt + 1 tok

    t0 = time.perf_counter()
    for i in range(gen - 1):
        nxt, cache = serve(params, cache, nxt,
                           jnp.full((b,), p + i, jnp.int32), rng)
    jax.block_until_ready(nxt)
    t_dec = time.perf_counter() - t0
    return {
        "mode": "sequential",
        "prefill_dispatches_per_request": dispatches,
        "ttft_s": t_first,
        "decode_tok_per_s": b * (gen - 1) / max(t_dec, 1e-9),
        "total_s": t_first + t_dec,
        "tokens_out": b * gen,
    }


def bench_batched_prefill(model, cfg, params, prompts, gen: int):
    b, p = prompts.shape
    prefill = jax.jit(steps_mod.make_prefill_step(model, cfg))
    serve = jax.jit(steps_mod.make_serve_step(model, cfg))
    lengths = jnp.full((b,), p, jnp.int32)
    rng = jax.random.PRNGKey(0)
    # warmup compiles
    cache = model.init_cache(cfg, b, p + gen + 1)
    warm, wcache = prefill(params, cache, prompts, lengths)
    serve(params, wcache, jnp.argmax(warm, -1).astype(jnp.int32),
          lengths, rng)[0].block_until_ready()

    cache = model.init_cache(cfg, b, p + gen + 1)
    t0 = time.perf_counter()
    last, cache = prefill(params, cache, prompts, lengths)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_first = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(gen - 1):
        tok, cache = serve(params, cache, tok,
                           jnp.full((b,), p + i, jnp.int32), rng)
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0
    return {
        "mode": "batched_prefill",
        "prefill_dispatches_per_request": 1,
        "ttft_s": t_first,
        "decode_tok_per_s": b * (gen - 1) / max(t_dec, 1e-9),
        "total_s": t_first + t_dec,
        "tokens_out": b * gen,
    }


def bench_continuous(model, cfg, params, n_slots: int, prompt_len: int,
                     gen: int, n_requests: int, paged: bool = False,
                     block_size: int = 16, n_blocks=None, spec_k: int = 0,
                     draft_depth=None, mode: str = None,
                     force_fused: bool = False):
    """Ragged Poisson-ish stream: arrivals are interleaved with ticks.

    Returns (row, requests) so the paged run can be checked token-for-token
    against the dense run and the pool can be sized from actual demand.
    ``spec_k > 0`` serves the same workload speculatively (truncated-cascade
    self-draft at ``draft_depth``).  ``force_fused`` routes paged attention
    through the fused streaming kernel regardless of backend.
    """
    reqs = make_ragged_requests(cfg.vocab_size, n_requests, prompt_len, gen,
                                vary_budget=True)
    # exponential inter-arrival gaps measured in ticks
    rs = np.random.RandomState(1)
    gaps = rs.exponential(scale=max(gen / (2 * n_slots), 0.5),
                          size=n_requests)
    arrive_at = np.floor(np.cumsum(gaps)).astype(int)

    was_forced = paged_attn.FORCE_FUSED
    paged_attn.FORCE_FUSED = force_fused or was_forced
    dispatches_before = dict(ops.PAGED_ATTN_DISPATCHES)
    try:
        eng = Engine(model, cfg, params, n_slots=n_slots,
                     max_len=prompt_len + gen + 1,
                     max_prompt_len=prompt_len,
                     paged=paged, block_size=block_size, n_blocks=n_blocks,
                     spec_k=spec_k, draft_depth=draft_depth)
        # warmup both compiled programs on a throwaway request, then
        # snapshot the stats so the report covers only the timed workload
        warm = Request(rid=10**6, prompt=[1, 2, 3], max_new_tokens=2)
        eng.run([warm], max_ticks=50)
        warm_stats = dict(eng.stats)

        t0 = time.perf_counter()
        nxt = 0
        tick = 0
        limit = n_requests * (prompt_len + gen) + 64
        while nxt < n_requests or eng.has_work:
            while nxt < n_requests and arrive_at[nxt] <= tick:
                eng.submit(reqs[nxt])
                nxt += 1
            eng.tick()
            tick += 1
            if tick > limit:
                raise RuntimeError("engine not drained")
        dt = time.perf_counter() - t0
    finally:
        paged_attn.FORCE_FUSED = was_forced
    toks = sum(len(r.generated) for r in reqs)
    # the first token of every request is sampled from the prefill logits;
    # only the rest are decode-step output, and only decode-step time pays
    # for them — same basis as the sequential/batched rows
    decode_toks = toks - n_requests
    decode_s = eng.stats["decode_s"] - warm_stats["decode_s"]
    ttft = [r.t_first_token - r.t_submit for r in reqs]
    if mode is None:
        mode = "spec" if spec_k else ("paged" if paged else "continuous")
    row = {
        "mode": mode,
        "prefill_dispatches_per_request": 1,
        "prefill_dispatches_total": eng.stats["prefill_dispatches"]
        - warm_stats["prefill_dispatches"],
        "decode_ticks": eng.stats["decode_ticks"]
        - warm_stats["decode_ticks"],
        "ttft_s": float(np.median(ttft)),
        "ttft_max_s": float(np.max(ttft)),
        "decode_tok_per_s": decode_toks / max(decode_s, 1e-9),
        "decode_s": decode_s,
        "prefill_s": eng.stats["prefill_s"] - warm_stats["prefill_s"],
        "total_s": dt,
        "tokens_out": toks,
        "n_requests": n_requests,
        "cache_bytes": eng.cache_bytes,
    }
    if paged:
        row.update({
            "block_size": eng.block_size,
            "pool_blocks": eng.allocator.n_blocks,
            "dense_parity_blocks": n_slots * eng.max_blocks,
            "max_blocks_per_slot": eng.max_blocks,
            "peak_blocks_in_use": eng.allocator.peak_in_use,
            "stalled_slot_ticks": eng.stats["stalled_slot_ticks"]
            - warm_stats["stalled_slot_ticks"],
            "preempted": eng.stats["preempted"] - warm_stats["preempted"],
            "attn_dispatches": {
                k: ops.PAGED_ATTN_DISPATCHES[k] - dispatches_before[k]
                for k in dispatches_before},
        })
    if spec_k:
        drafted = eng.stats["drafted"] - warm_stats["drafted"]
        accepted = eng.stats["accepted"] - warm_stats["accepted"]
        row.update({
            "spec_k": spec_k,
            "draft_depth": eng.draft.depth,
            "drafted": drafted,
            "accepted": accepted,
            "acceptance_rate": accepted / max(drafted, 1),
            # target-model dispatches per generated decode token: the
            # speculative win — one verify advances a slot several tokens
        })
    row["target_dispatches_per_token"] = (row["decode_ticks"]
                                          / max(decode_toks, 1))
    return row, reqs


def pool_blocks_for_mix(reqs, n_slots: int, prompt_len: int, gen: int,
                        block_size: int) -> int:
    """Size the paged pool from the workload mix: the sum of the top
    ``n_slots`` per-request page demands bounds what any concurrent slot
    set can hold, so this pool can never deadlock — yet it is far below
    dense parity whenever the mix is ragged (the whole point of paging).
    """
    max_len = prompt_len + gen + 1
    demands = sorted(
        (-(-min(r.prompt_len + r.max_new_tokens + 1, max_len) // block_size)
         for r in reqs),
        reverse=True)
    return sum(demands[:n_slots])


def bench_overload(args):
    """Overload mode (``--overload``): the page pool is sized to ~60% of
    the workload mix's demand, arrivals come in faster than the engine
    drains (jittered Poisson gaps), and half the stream carries deadlines
    across three priority bands — so the resilience machinery, not the
    steady-state path, carries the run: admissions gate, slots stall,
    deadlocks break by preempt-and-requeue, queued SLOs time out, and the
    degradation ladder may bound the queue.

    Reports p50/p99 TTFT and TPOT over requests that got a first token
    plus the preempt / requeue / timeout / shed counters, and asserts the
    overload guarantees: every request reaches a terminal state, NO
    request is killed with ``cache_full`` (the seed's behaviour when the
    pool deadlocked — requeue-with-recompute replaces it), and the page
    pool comes back leak-free.

    The latency percentiles are read from the engine's shared obs
    histograms (``serve_ttft_seconds`` / ``serve_tpot_seconds``) and
    cross-checked against the raw per-request lists to within one
    histogram bin width — the log-bin accuracy contract in
    ``repro/obs/metrics.py``.  The run is span-traced; the Chrome trace
    lands in ``results/TRACE_serve_overload.json``.  ``--spec`` serves
    the overload stream speculatively (ACDC SELL smoke model,
    truncated-cascade self-draft) so the trace also covers the
    draft/verify path.
    """
    cfg = registry.get_smoke_config(args.arch)
    if args.spec:
        # speculation needs cascades to truncate (see bench_spec)
        cfg = dataclasses.replace(cfg, sell_kind="acdc", sell_k=4,
                                  sell_permute=False, sell_init_std=0.02)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    n, gen = args.requests, args.gen
    reqs = make_ragged_requests(
        cfg.vocab_size, n, args.prompt_len, gen, seed=2, vary_budget=True,
        deadline_range=(2.0, 10.0), deadline_frac=0.5, n_priorities=3)
    demand = pool_blocks_for_mix(reqs, args.slots, args.prompt_len, gen,
                                 args.block_size)
    # max_prompt_len covers prompt + full generation so ANY active request
    # can re-prefill after preemption: under overload the engine must
    # always be able to trade latency instead of killing streams
    max_prompt = args.prompt_len + gen
    min_pool = -(-(max_prompt + 1) // args.block_size)
    pool = max(min_pool, int(0.6 * demand))
    tracer = SpanTracer()
    set_global_tracer(tracer)       # allocator audits ride along
    obs = Observability(tracer=tracer)
    eng = Engine(model, cfg, params, n_slots=args.slots,
                 max_len=max_prompt + 1, max_prompt_len=max_prompt,
                 paged=True, block_size=args.block_size, n_blocks=pool,
                 spec_k=args.spec_k if args.spec else 0, obs=obs)
    warm = Request(rid=10**6, prompt=[1, 2, 3], max_new_tokens=2)
    eng.run([warm], max_ticks=50)
    # exclude the compile-warmup request from the reported percentiles
    h_ttft = obs.registry.get("serve_ttft_seconds")
    h_tpot = obs.registry.get("serve_tpot_seconds")
    h_ttft.reset()
    h_tpot.reset()

    # arrivals ~2x faster than the continuous bench: sustained overload
    rs = np.random.RandomState(4)
    gaps = rs.exponential(scale=max(gen / (4 * args.slots), 0.25), size=n)
    arrive_at = np.floor(np.cumsum(gaps)).astype(int)
    t0 = time.perf_counter()
    nxt = 0
    tick = 0
    limit = 4 * n * (args.prompt_len + gen) + 256
    while nxt < n or eng.has_work:
        while nxt < n and arrive_at[nxt] <= tick:
            eng.submit(reqs[nxt])
            nxt += 1
        eng.tick()
        tick += 1
        if tick > limit:
            raise RuntimeError("overload run not drained")
    dt = time.perf_counter() - t0

    assert all(r.done for r in reqs), "request left non-terminal"
    reasons = {}
    for r in reqs:
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    assert reasons.get("cache_full", 0) == 0, (
        "overload killed a stream with cache_full — preempt-requeue "
        "should have recomputed it")
    eng.allocator.audit()
    assert eng.allocator.n_free == eng.allocator.n_blocks

    # latency percentiles come from the SHARED obs histograms; the raw
    # per-request lists only cross-check them (within one bin width, the
    # histogram's documented accuracy)
    served = [r.t_first_token - r.t_submit for r in reqs
              if r.t_first_token is not None]
    tpot = [(r.t_finish - r.t_first_token) / (len(r.generated) - 1)
            for r in reqs if r.t_first_token is not None
            and r.t_finish is not None and len(r.generated) > 1]
    for h, raw in ((h_ttft, served), (h_tpot, tpot)):
        assert h.count == len(raw), (
            f"{h.name}: {h.count} observations vs {len(raw)} requests")
        for q in (50.0, 99.0):
            hp = h.percentile(q)
            lp = float(np.percentile(raw, q)) if raw else None
            if hp is None or lp is None:
                assert hp is None and lp is None
                continue
            tol = max(h.bin_width(hp), h.bin_width(lp))
            assert abs(hp - lp) <= tol, (
                f"{h.name} p{q:.0f}: histogram {hp:.4f} vs list {lp:.4f} "
                f"exceeds one bin width ({tol:.4f})")

    os.makedirs(RESULTS, exist_ok=True)
    trace_path = os.path.join(RESULTS, "TRACE_serve_overload.json")
    tracer.write(trace_path)
    set_global_tracer(None)
    names = {e["name"] for e in tracer.chrome_trace()["traceEvents"]}
    assert {"queued", "prefill", "decode"} <= names, (
        f"trace missing lifecycle spans: {sorted(names)}")
    if eng.stats["preempted"]:
        assert {"preempt", "backoff"} <= names
    if eng.stats["degrade_down"]:
        assert "ladder" in names
    for r in reqs:
        assert len(tracer.terminals_for(r.rid)) == 1, (
            f"rid={r.rid}: expected exactly one terminal event")

    row = {
        "mode": "overload",
        "n_requests": n,
        "pool_blocks": pool,
        "pool_vs_demand": pool / max(demand, 1),
        "finish_reasons": reasons,
        "ttft_p50_s": h_ttft.percentile(50),
        "ttft_p99_s": h_ttft.percentile(99),
        "ttft_p50_list_s": (float(np.percentile(served, 50))
                            if served else None),
        "ttft_p99_list_s": (float(np.percentile(served, 99))
                            if served else None),
        "tpot_p50_s": h_tpot.percentile(50),
        "tpot_p99_s": h_tpot.percentile(99),
        "trace_out": os.path.relpath(trace_path),
        "preempted": eng.stats["preempted"],
        "requeued": eng.stats["requeued"],
        "deadline_preempts": eng.stats["deadline_preempts"],
        "timeout": eng.stats["timeout"],
        "rejected": eng.stats["rejected"],
        "stalled_slot_ticks": eng.stats["stalled_slot_ticks"],
        "degrade_down": eng.stats["degrade_down"],
        "degrade_up": eng.stats["degrade_up"],
        "tokens_out": sum(len(r.generated) for r in reqs),
        "total_s": dt,
    }
    if args.spec:
        row.update({
            "spec_k": args.spec_k,
            "drafted": eng.stats["drafted"],
            "accepted": eng.stats["accepted"],
            "acceptance_rate": eng.stats["acceptance_rate"],
        })
    return row


def bench_spec(args):
    """Speculative vs non-speculative on an ACDC SELL smoke model.

    The truncated-cascade draft needs cascades to truncate, so this runs
    on the smoke config with ``sell_kind='acdc'`` (K = 4, un-riffled, a
    near-converged ``sell_init_std`` so the truncated tail approximates
    the target the way a trained cascade does — see spec/draft.py on why
    riffled cascades truncate poorly).  The greedy spec stream must be
    token-identical to the baseline while spending strictly fewer target
    dispatches per generated token.
    """
    cfg = dataclasses.replace(
        registry.get_smoke_config(args.arch), sell_kind="acdc", sell_k=4,
        sell_permute=False, sell_init_std=0.02)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    base, base_reqs = bench_continuous(
        model, cfg, params, args.slots, args.prompt_len, args.gen,
        args.requests, mode="spec_baseline")
    spec, spec_reqs = bench_continuous(
        model, cfg, params, args.slots, args.prompt_len, args.gen,
        args.requests, spec_k=args.spec_k, draft_depth=2)
    for b, s in zip(base_reqs, spec_reqs):
        assert s.generated == b.generated, (
            f"rid={b.rid}: spec stream diverged from baseline")
    assert (spec["target_dispatches_per_token"]
            < base["target_dispatches_per_token"]), (
        "speculation did not reduce target dispatches per token")
    return [base, spec]


def main(csv: bool = True, argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b", choices=registry.ARCHS)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=12)
    # 8-token pages: at smoke scale the coarser 16-token granularity plus
    # the trash page can round a ragged mix back above the dense footprint
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--spec", action="store_true",
                    help="also A/B speculative decoding (truncated-cascade "
                         "draft) against the continuous baseline on an "
                         "ACDC SELL smoke model")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--overload", action="store_true",
                    help="run ONLY the overload-resilience benchmark: pool "
                         "below the mix's demand, jittered Poisson "
                         "arrivals, deadlines + priorities; reports "
                         "p50/p99 TTFT and preempt/requeue/timeout/shed "
                         "counts and asserts zero cache_full kills")
    args = ap.parse_args(argv)

    if args.overload:
        row = bench_overload(args)
        os.makedirs(RESULTS, exist_ok=True)
        path = os.path.join(RESULTS, "BENCH_serve_overload.json")
        out = {"backend": jax.default_backend(),
               "timing": timing_meta(1, 1),
               "arch": args.arch, "slots": args.slots,
               "prompt_len": args.prompt_len, "gen": args.gen,
               "block_size": args.block_size, "overload": row}
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        if csv:
            fr = ";".join(f"{k}:{v}" for k, v in
                          sorted(row["finish_reasons"].items()))
            print(f"serve_overload,{row['total_s'] * 1e6:.0f},"
                  f"ttft_p50_s={row['ttft_p50_s']:.3f};"
                  f"ttft_p99_s={row['ttft_p99_s']:.3f};"
                  f"tpot_p50_s={row['tpot_p50_s']:.4f};"
                  f"tpot_p99_s={row['tpot_p99_s']:.4f};"
                  f"requeued={row['requeued']};timeout={row['timeout']};"
                  f"rejected={row['rejected']};reasons={fr}")
            print(f"wrote {os.path.relpath(path)}")
            print(f"wrote {row['trace_out']}")
        return out

    cfg = registry.get_smoke_config(args.arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.slots, args.prompt_len), 0,
        cfg.vocab_size, jnp.int32)

    cont, cont_reqs = bench_continuous(
        model, cfg, params, args.slots, args.prompt_len, args.gen,
        args.requests)
    pool = pool_blocks_for_mix(cont_reqs, args.slots, args.prompt_len,
                               args.gen, args.block_size)
    paged, paged_reqs = bench_continuous(
        model, cfg, params, args.slots, args.prompt_len, args.gen,
        args.requests, paged=True, block_size=args.block_size,
        n_blocks=pool)
    fused, fused_reqs = bench_continuous(
        model, cfg, params, args.slots, args.prompt_len, args.gen,
        args.requests, paged=True, block_size=args.block_size,
        n_blocks=pool, force_fused=True, mode="paged_fused")
    rows = [
        bench_sequential(model, cfg, params, prompts, args.gen),
        bench_batched_prefill(model, cfg, params, prompts, args.gen),
        cont,
        paged,
        fused,
    ]
    if args.spec:
        rows += bench_spec(args)
    seq, bat = rows[0], rows[1]
    assert bat["prefill_dispatches_per_request"] == 1
    assert seq["prefill_dispatches_per_request"] == args.prompt_len
    # paged acceptance: same tokens out of a strictly smaller cache
    assert paged["preempted"] == 0
    assert paged["cache_bytes"] < cont["cache_bytes"], (
        f"paged pool {paged['cache_bytes']}B not below dense "
        f"{cont['cache_bytes']}B")
    for d, p in zip(cont_reqs, paged_reqs):
        assert p.generated == d.generated, (
            f"rid={d.rid}: paged stream diverged from dense")
    # fused-kernel acceptance: token-identical to the gather run, only
    # fused dispatches recorded
    for g, f in zip(paged_reqs, fused_reqs):
        assert f.generated == g.generated, (
            f"rid={g.rid}: paged_fused stream diverged from paged")
    assert fused["attn_dispatches"]["fused"] > 0
    assert fused["attn_dispatches"]["gather"] == 0, (
        "paged_fused run fell back to the gather path")

    out = {
        "backend": jax.default_backend(),
        # off-TPU the fused kernel runs interpreted: wall-clock rows are
        # dispatch/fusion structure, not kernel roofline numbers
        "non_roofline": jax.default_backend() != "tpu",
        "timing": timing_meta(1, 1),
        "arch": cfg.name,
        "slots": args.slots,
        "prompt_len": args.prompt_len,
        "gen": args.gen,
        "block_size": args.block_size,
        "modes": rows,
        "ttft_speedup_batched_vs_sequential":
            seq["ttft_s"] / max(bat["ttft_s"], 1e-9),
        "paged_cache_bytes_vs_dense":
            paged["cache_bytes"] / max(cont["cache_bytes"], 1),
    }
    if args.spec:
        sbase, srow = rows[-2], rows[-1]
        out["spec_acceptance_rate"] = srow["acceptance_rate"]
        out["spec_dispatches_per_token_vs_baseline"] = (
            srow["target_dispatches_per_token"]
            / max(sbase["target_dispatches_per_token"], 1e-9))
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    if csv:
        for r in rows:
            extra = ""
            if r["mode"] == "paged":
                extra = (f";cache_bytes={r['cache_bytes']}"
                         f"(dense={cont['cache_bytes']})"
                         f";peak_blocks={r['peak_blocks_in_use']}"
                         f"/{r['pool_blocks']}")
            if r["mode"] == "paged_fused":
                extra = (f";dispatches=fused:{r['attn_dispatches']['fused']}"
                         f"/gather:{r['attn_dispatches']['gather']}")
            if r["mode"] == "spec":
                extra = (f";acceptance={r['acceptance_rate']:.3f}"
                         f";dispatches_per_tok="
                         f"{r['target_dispatches_per_token']:.3f}")
            print(f"serve_{r['mode']},{r['total_s'] * 1e6:.0f},"
                  f"tok_per_s={r['decode_tok_per_s']:.1f};"
                  f"ttft_s={r['ttft_s']:.3f};"
                  f"prefill_dispatches={r['prefill_dispatches_per_request']}"
                  + extra)
        print(f"wrote {os.path.relpath(path)}")
    return out


if __name__ == "__main__":
    main()
