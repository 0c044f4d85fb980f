"""Serving launcher: continuous-batching engine over the model zoo.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1_7b --smoke \
        --slots 4 --prompt-len 32 --gen 32 --requests 12

Each request gets a random ragged-length prompt; the engine admits them
into batch slots (one lowered prefill program per admission), advances all
active slots with one fused decode step per tick, and evicts finished
requests so the batch stays full.  ``--static`` falls back to plain
batched prefill + lockstep decode (no continuous batching) for A/B runs.
``--paged --block-size 16 [--blocks N]`` serves from the paged block KV
cache: all slots draw pages from one global pool sized for the traffic
mix instead of each reserving a dense ``max_len`` slab.
``--spec [--spec-k 4] [--draft-depth K/2] [--spec-skip-layers J]`` turns
on speculative decoding: the target's own truncated ACDC cascades draft
``spec-k`` tokens per tick and one verify program scores them all, so
each slot advances by its accepted length per target dispatch (greedy
streams are bit-identical to the non-speculative engine).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import cache as cache_mod
from repro.configs import registry
from repro.dist import steps as steps_mod
from repro.models import get_model
from repro.obs import (
    REGISTRY,
    JsonlExporter,
    Observability,
    Prof,
    ProfileWindow,
    Registry,
    SpanTracer,
    set_global_tracer,
)
from repro.serving import Engine
from repro.serving.request import make_ragged_requests


def _make_frontend(cfg, rng, batch: int):
    if cfg.family == "encdec":
        frames = cfg.n_frontend_tokens or 16
        return jax.random.normal(rng, (batch, frames, cfg.d_model))
    return None


def run_static(model, cfg, params, args, prompts, rng):
    """Batched prefill then lockstep greedy decode (no slot reuse)."""
    b, p, g = args.slots, args.prompt_len, args.gen
    max_len = p + g + 1
    cache = model.init_cache(cfg, b, max_len)
    fe = _make_frontend(cfg, rng, b)
    prefill = jax.jit(steps_mod.make_prefill_step(model, cfg))
    serve = jax.jit(steps_mod.make_serve_step(
        model, cfg, sample=args.sample, temperature=args.temperature,
        top_k=args.top_k, top_p=args.top_p))

    from repro.serving import sampler as sampler_mod

    t0 = time.time()
    lengths = jnp.full((b,), p, jnp.int32)
    last, cache = prefill(params, cache, prompts, lengths, fe)
    tok = sampler_mod.sample(rng, last, method=args.sample,
                             temperature=args.temperature,
                             top_k=args.top_k, top_p=args.top_p)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out = [tok]
    t0 = time.time()
    for i in range(g - 1):
        pos = jnp.full((b,), p + i, jnp.int32)
        tok, cache = serve(params, cache, tok, pos,
                           jax.random.fold_in(rng, i))
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    gen = jnp.stack(out, axis=1)
    print(f"[static] prefill {p}x{b} toks in ONE dispatch: {t_prefill:.2f}s | "
          f"decode {g - 1} steps: {dt:.2f}s ({b * (g - 1) / max(dt, 1e-9):.1f} tok/s)")
    print("sample generations (token ids):")
    for row in gen[: min(b, 2)]:
        print("  ", row[:16].tolist())


def build_obs(args) -> Observability:
    """Assemble the observability bundle from the launcher flags.

    With no obs flags set this returns ``Observability.off()`` — the
    engine's documented noop fast path (see ``repro/obs/__init__.py``).
    The engine owns the per-engine registry built here; the JSON-lines
    exporter merges in the process-global ``REGISTRY`` snapshot so the
    kernels' trace-time dispatch counters ride along.
    """
    if not (args.metrics_jsonl or args.trace_out or args.profile_ticks):
        return Observability.off()
    reg = Registry()
    tracer = None
    if args.trace_out:
        # clock=None: the tracer adopts the engine's clock at attach
        tracer = SpanTracer()
        set_global_tracer(tracer)
    exporter = None
    if args.metrics_jsonl:
        exporter = JsonlExporter(args.metrics_jsonl, reg,
                                 every=args.metrics_every,
                                 clock=time.time,
                                 extra_snapshots=(REGISTRY.snapshot,))
    window = None
    prof = None
    if args.profile_ticks:
        window = ProfileWindow(args.profile_ticks, args.profile_logdir)
        prof = Prof(enabled=True)
    return Observability(registry=reg, tracer=tracer, exporter=exporter,
                         prof=prof, window=window)


def run_engine(model, cfg, params, args, rng):
    """Serve ``args.requests`` ragged requests through one ``Engine`` and
    print its summary; returns ``(engine, requests)`` for the caller to
    inspect."""
    obs = build_obs(args)
    eng = Engine(model, cfg, params, n_slots=args.slots,
                 max_len=args.prompt_len + args.gen + 1,
                 max_prompt_len=args.prompt_len, sample=args.sample,
                 temperature=args.temperature, top_k=args.top_k,
                 top_p=args.top_p, paged=args.paged,
                 block_size=args.block_size, n_blocks=args.blocks,
                 spec_k=args.spec_k if args.spec else 0,
                 draft_depth=args.draft_depth,
                 draft_skip_layers=args.spec_skip_layers,
                 obs=obs)
    if args.spec:
        print(f"[spec] k={eng.spec_k} draft={type(eng.draft).__name__} "
              f"depth={getattr(eng.draft, 'depth', '-')} "
              f"skip_layers={getattr(eng.draft, 'skip_layers', 0)}")
    if args.paged:
        print(f"[paged] block_size={eng.block_size} "
              f"pool={eng.allocator.n_blocks} blocks "
              f"(dense parity {args.slots * eng.max_blocks}) | "
              f"cache {eng.cache_bytes / 1e6:.2f} MB")
    deadline_range = None
    if args.deadline_s is not None:
        deadline_range = (args.deadline_s, args.deadline_s)
    reqs = make_ragged_requests(cfg.vocab_size, args.requests,
                                args.prompt_len, args.gen,
                                deadline_range=deadline_range,
                                deadline_frac=args.deadline_frac,
                                n_priorities=args.priorities)
    if cfg.family == "encdec":
        for req in reqs:
            req.frontend_embeds = _make_frontend(
                cfg, jax.random.fold_in(jax.random.PRNGKey(7), req.rid), 1)

    t0 = time.time()
    eng.run(reqs,
            max_ticks=4 * args.requests * (args.prompt_len + args.gen) + 64,
            wall_clock_limit_s=args.wall_clock_limit_s)
    dt = time.time() - t0
    if eng.wall_clock_exceeded:
        print(f"[engine] WALL CLOCK LIMIT ({args.wall_clock_limit_s}s) hit: "
              f"partial results")
    toks = eng.stats["tokens_out"]
    ttft = [r.t_first_token - r.t_submit for r in reqs
            if r.t_first_token is not None]
    print(f"[engine] {len(reqs)} ragged requests | "
          f"{eng.stats['prefill_dispatches']} prefill dispatches | "
          f"{eng.stats['decode_ticks']} decode ticks | "
          f"{toks} tokens in {dt:.2f}s ({toks / max(dt, 1e-9):.1f} tok/s)")
    if args.paged:
        print(f"[paged] peak {eng.allocator.peak_in_use}/"
              f"{eng.allocator.n_blocks} blocks in use | "
              f"{eng.stats['stalled_slot_ticks']} stalled slot-ticks | "
              f"{eng.stats['preempted']} preempted")
    s = eng.stats
    if (s["requeued"] or s["timeout"] or s["rejected"]
            or s["degrade_down"]):
        print(f"[resilience] {s['requeued']} requeued "
              f"({s['deadline_preempts']} for deadlines) | "
              f"{s['timeout']} timed out | {s['rejected']} shed | "
              f"ladder down/up {s['degrade_down']}/{s['degrade_up']} "
              f"(now {eng.degrade_level})")
    if args.spec:
        print(f"[spec] {eng.stats['accepted']}/{eng.stats['drafted']} "
              f"drafts accepted (rate "
              f"{eng.stats['acceptance_rate']:.3f}) | "
              f"{eng.stats['decode_ticks']} verify dispatches for "
              f"{toks} tokens "
              f"({toks / max(eng.stats['decode_ticks'], 1):.2f} tok/dispatch)")
    if ttft:
        print(f"[engine] ttft p50 {np.median(ttft):.3f}s "
              f"max {max(ttft):.3f}s")
    print("sample generations (token ids):")
    for r in reqs[:2]:
        print(f"   rid={r.rid} len={r.prompt_len} "
              f"finish={r.finish_reason}: {r.generated[:16]}")

    obs.close()
    if obs.tracer is not None:
        obs.tracer.write(args.trace_out)
        print(f"[obs] chrome trace -> {args.trace_out} "
              f"(load in chrome://tracing or ui.perfetto.dev)")
    if obs.exporter is not None:
        print(f"[obs] metrics jsonl -> {args.metrics_jsonl} "
              f"({obs.exporter.exports} snapshots)")
    if obs.window is not None:
        print(f"[obs] profiler capture -> {args.profile_logdir} "
              f"(ticks {args.profile_ticks})")
    return eng, reqs


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b", choices=registry.ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sell", default="dense")
    ap.add_argument("--sell-method", default="auto",
                    choices=["auto", "fft", "matmul", "pallas"],
                    help="transform backend for SELL projections")
    ap.add_argument("--sell-transform", default="acdc",
                    help="transform family for --sell acdc cascades "
                         "(core/families.py: acdc | circulant | hadamard)")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--sample", default="greedy", choices=["greedy", "temp"])
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--static", action="store_true",
                    help="batched prefill + lockstep decode, no slot reuse")
    ap.add_argument("--paged", action="store_true",
                    help="paged block KV cache: slots draw fixed-size pages "
                         "from one global pool instead of each reserving "
                         "a dense max_len slab")
    ap.add_argument("--block-size", type=int, default=16,
                    help="token positions per KV page (paged mode)")
    ap.add_argument("--blocks", type=int, default=None,
                    help="pool size in pages; default = dense parity "
                         "(slots * ceil(max_len / block_size))")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding: truncated-cascade "
                         "self-draft + one batched k-token verify per tick")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative tick")
    ap.add_argument("--draft-depth", type=int, default=None,
                    help="cascade layers the draft keeps "
                         "(default sell_k // 2)")
    ap.add_argument("--spec-skip-layers", type=int, default=0,
                    help="also drop this many top transformer blocks "
                         "from the draft (decoder families)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="give a fraction of requests this latency SLO; "
                         "admission turns earliest-deadline-first and "
                         "requests past the deadline finish as timeouts")
    ap.add_argument("--deadline-frac", type=float, default=0.5,
                    help="fraction of requests carrying --deadline-s")
    ap.add_argument("--priorities", type=int, default=1,
                    help="priority bands drawn uniformly per request "
                         "(ties in deadline order; shed order under "
                         "overload)")
    ap.add_argument("--wall-clock-limit-s", type=float, default=None,
                    help="hard bound on the serve loop's real time; exits "
                         "with partial results instead of hanging")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="append periodic registry snapshots (JSON lines) "
                         "to PATH; off when unset")
    ap.add_argument("--metrics-every", type=int, default=50,
                    help="ticks between --metrics-jsonl snapshots")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write per-request span tracing as Chrome "
                         "trace-event JSON to PATH; off when unset")
    ap.add_argument("--profile-ticks", default=None, metavar="A:B",
                    help="capture a jax.profiler trace across engine "
                         "ticks A..B inclusive (see --profile-logdir)")
    ap.add_argument("--profile-logdir", default="results/profile",
                    help="destination for the --profile-ticks capture")
    args = ap.parse_args(argv)
    if args.paged and args.static:
        ap.error("--paged applies to the engine path, not --static")
    if args.spec and args.static:
        ap.error("--spec applies to the engine path, not --static")
    if args.static and (args.metrics_jsonl or args.trace_out
                        or args.profile_ticks):
        ap.error("--metrics-jsonl/--trace-out/--profile-ticks apply to "
                 "the engine path, not --static")
    return args


def build_model(args, rng):
    """``(cfg, model, params)`` for the launcher flags, params drawn
    from ``rng``."""
    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    cfg = registry.with_sell(cfg, args.sell, method=args.sell_method,
                             transform=args.sell_transform)
    model = get_model(cfg)
    return cfg, model, model.init(rng, cfg)


def main(argv=None):
    cache_mod.configure_compile_cache()
    args = parse_args(argv)
    rng = jax.random.PRNGKey(0)
    cfg, model, params = build_model(args, rng)
    print(f"arch={cfg.name} sell={cfg.sell_kind} slots={args.slots}")

    if args.static:
        prompts = jax.random.randint(
            rng, (args.slots, args.prompt_len), 0, cfg.vocab_size, jnp.int32)
        run_static(model, cfg, params, args, prompts, rng)
    else:
        run_engine(model, cfg, params, args, rng)


if __name__ == "__main__":
    main()
