"""Run the serving and training paths once on a TPU and check the results.

    python chip_smoke.py               # one chip: serve + train phases
    python chip_smoke.py --four-chips  # the (data=2, model=2) train mesh

One chip: ``qwen3_1_7b`` at its published widths (28 layers, d_model
2048, 16/8 heads, d_ff 6144, vocab 151936) with random weights from a
fixed seed, driven through the launchers' own entry points:

* serve: ``launch/serve.run_engine`` with the ACDC projections on the
  Pallas kernels (``--sell acdc --sell-method pallas``), paged KV in
  16-token pages, 8 requests of 256-1024 prompt tokens and 32 new tokens
  over 4 slots; the same requests in fp32 on paged and on contiguous KV;
  then with dense projections (the baseline);
* train: 3 steps of ``launch/train.main`` with the ACDC projections on
  the Pallas kernels, full depth, the batch cut to 2 x 256 tokens.

Checks (each a failure when it does not hold): every request finishes by
``length`` or ``eos`` with ids inside the vocabulary; paged attention
took the fused kernel and never the gather; every paged token (in bf16,
all but ``BF16_OUTLIER_SHARE`` of them) is within ``TIE_TOL`` row
standard deviations of the top logit of a full-sequence forward
(``model.apply``, no KV cache) on the same context; in fp32 the paged and
the contiguous engine agree token for token up to their first
difference, where both tokens are within that bound; the fused
paged-attention kernel at the served head shapes and pool dtype matches
its gather oracle within ``KERNEL_TOL`` and writes the pools bit for bit;
the Pallas prefill logits agree with ``sell_method="matmul"`` on the same
parameters within ``PALLAS_TOL``; the training loss is finite.

``--four-chips`` runs only the multi-chip training path: the train steps
on the launcher's (data=2, model=2) mesh, then the same steps with the
same seed and batch on one device.  The per-step losses must agree within
``LOSS_TOL``, no device may hold the whole train state, and on the
network with all but ``LIVE_LAYERS`` blocks silenced every gradient leaf
must agree within ``GRAD_TOL``.  The gradients of the full random network
are printed beside one device's own rounding spread, not bounded: at 28
random layers they move by tens of percent under a one-rounding change of
the weights.

Each phase prints a ``[phase]`` JSON line with its XLA compile seconds,
the rest of its wall seconds and the device's ``peak_bytes_in_use``.
The last line of stdout is ``{"ok": true, "device": {...}}``; a failed
check exits 1 without it, and a host without a TPU exits 2 before any
phase runs.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import cache as cache_mod  # noqa: E402
from repro.dist import steps as steps_mod  # noqa: E402
from repro.kernels import ops, paged_attn, ref  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.optim import tree_paths  # noqa: E402

#: the model and the launcher flags every phase shares
MODEL = ["--arch", "qwen3_1_7b"]
ACDC = ["--sell", "acdc", "--sell-method", "pallas"]
DENSE = ["--sell", "dense"]
#: prompts of 256-1024 tokens (make_ragged_requests draws from
#: [prompt_len / 4, prompt_len]), 32 new tokens, 4 slots
SERVE = ["--slots", "4", "--prompt-len", "1024", "--gen", "32",
         "--requests", "8"]
PAGED = ["--paged", "--block-size", "16"]
#: all 28 layers; the batch is cut to 2 x 256 tokens, which the one-chip
#: compile puts at about 11 GB of the 16 GB
TRAIN = ["--steps", "3", "--seq-len", "256", "--global-batch", "2"]

#: a chosen token may sit this many standard deviations of its logit row
#: below the row's top logit: rounding moves near-ties by a few
#: hundredths of one, a wrong page or mask by whole ones (the top logit of
#: 151936 sits about 4.5 above the row mean)
TIE_TOL = 0.25
#: share of bf16 tokens allowed beyond ``TIE_TOL``.  At 28 random layers
#: two bf16 forwards of the same model part on some positions by whole
#: standard deviations (Pallas and matmul prefill agree on 90% of the
#: argmaxes), and one of 256 paged tokens did so on the v5e.  A bf16-only
#: fault in the pool's writes or reads would move nearly every token; the
#: paging logic itself is held to every token in fp32.
BF16_OUTLIER_SHARE = 0.02
#: relative L2 error of the Pallas prefill logits against the XLA matmul
#: path.  The matmul path rounds C and every product to bf16 where the
#: kernels keep fp32; at full width on the CPU the two differed by 2.5%,
#: 3.2% and 4.5% at 2, 4 and 8 layers, about sqrt(depth), so about 8% at
#: 28.  A wrong kernel is off by order one.
PALLAS_TOL = 0.15
#: relative L2 error of the fused paged-attention kernel's output against
#: its fp32 gather oracle; rounding the output to bf16 alone costs about
#: 2e-3, a wrong page, mask or head block order one
KERNEL_TOL = 2e-2
#: per-step loss difference between the four-chip mesh and one device
#: (the loss starts near ln(151936) = 11.9)
LOSS_TOL = 2e-2
#: blocks left live when the deeper ones are silenced (``silence``)
LIVE_LAYERS = 2
#: relative L2 gap per gradient leaf between the mesh and one device on
#: the silenced network.  Two live layers move their gradient by about 5%
#: under a relative 2**-9 change of every weight (CPU, vocabulary cut to
#: 8192), and on the v5e the mesh's sat 1.2% from one device's (at most
#: 1.5% in a leaf); a wrong reduction or a lost shard is off by order one
GRAD_TOL = 2e-2
#: relative weight perturbation that measures one device's own rounding
#: spread: about one bf16 rounding
PERTURB = 2.0 ** -9

#: JAX's monitoring event for one XLA backend compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds XLA spent compiling in this process, summed from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


def _memory_stats(device) -> dict:
    return device.memory_stats() or {}


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Time a phase and print its ``[phase]`` line."""
    c0, t0 = clock.seconds, time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    print("[phase] " + json.dumps({
        "phase": name, "compile_s": compile_s, "run_s": wall - compile_s,
        "peak_bytes_in_use": _memory_stats(jax.devices()[0]).get(
            "peak_bytes_in_use")}), flush=True)


def check_finished(reqs, vocab_size: int, label: str) -> list:
    """Every request ended by ``length``/``eos`` with in-vocabulary ids."""
    fails = []
    for r in reqs:
        if r.finish_reason not in ("length", "eos"):
            fails.append(f"{label}: request {r.rid} finished by "
                         f"{r.finish_reason!r}")
        if not r.generated or not all(0 <= t < vocab_size
                                      for t in r.generated):
            fails.append(f"{label}: request {r.rid} has ids outside "
                         f"[0, {vocab_size}) or none")
    return fails


def _routes() -> dict:
    return {"paged_attn": dict(ops.PAGED_ATTN_DISPATCHES.items()),
            "cascade_bwd": dict(ops.CASCADE_BWD_DISPATCHES.items())}


def check_route(before: dict, label: str) -> list:
    """Print the dispatch counters a run added; paged attention must
    have taken the fused kernel, never the gather."""
    after = _routes()
    added = {name: {k: after[name][k] - before[name][k] for k in counts}
             for name, counts in after.items()}
    print(f"[route] {label}: PAGED_ATTN_DISPATCHES "
          f"{added['paged_attn']} | CASCADE_BWD_DISPATCHES "
          f"{added['cascade_bwd']}", flush=True)
    paged = added["paged_attn"]
    if paged["gather"] or not paged["fused"]:
        return [f"{label}: paged attention routes {paged}, expected fused "
                f"only"]
    return []


def check_streams(model, cfg, params, reqs, length: int,
                  ref_reqs=None, label: str = "",
                  outlier_share: float = 0.0) -> list:
    """Judge the greedy streams ``reqs`` of a paged engine by a
    full-sequence forward (``model.apply``, no KV cache) on the same
    context: every token but an ``outlier_share`` of them is within
    ``TIE_TOL`` row standard deviations of that position's top logit.
    With ``ref_reqs``, the streams of a contiguous-cache engine on the
    same requests: they equal ``reqs`` up to their first difference, where
    the contiguous token is within the same bound (a near-tie that
    rounding broke the other way)."""
    gen = max(len(r.generated) for r in reqs)

    @jax.jit
    def rows(params, tokens, start):
        logits = model.apply(params, tokens, cfg)[0]
        return jax.lax.dynamic_slice_in_dim(logits, start, gen, axis=0)

    fails, outliers, same, total, worst = [], [], 0, 0, 0.0
    for j, r in enumerate(reqs):
        out = np.asarray(r.generated)
        seq = np.concatenate([np.asarray(r.prompt), out[:-1]])
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        lg = np.asarray(rows(params, tokens, len(r.prompt) - 1))[:len(out)]
        top, std = lg.max(-1), lg.std(-1)
        gap = (top - lg[np.arange(len(out)), out]) / std
        worst = max(worst, float(gap.max()))
        outliers += [f"request {r.rid} paged token {i} is {gap[i]:.3f} std "
                     f"below the top logit"
                     for i in np.nonzero(gap > TIE_TOL)[0]]
        total += len(out)
        if ref_reqs is None:
            continue
        ref = np.asarray(ref_reqs[j].generated)
        n = min(len(out), len(ref))
        diff = np.nonzero(out[:n] != ref[:n])[0]
        k = int(diff[0]) if len(diff) else n
        same += k
        if k < n:
            g = float((top[k] - lg[k, ref[k]]) / std[k])
            print(f"[check] {label}: request {r.rid} streams part at token "
                  f"{k}: paged {gap[k]:.4f}, contiguous {g:.4f} std below "
                  f"the top logit", flush=True)
            worst = max(worst, g)
            if g > TIE_TOL:
                fails.append(f"{label}: request {r.rid} contiguous token {k} "
                             f"is {g:.3f} std below the top logit (tol "
                             f"{TIE_TOL})")
    agreed = (f"; {same}/{total} tokens identical before the streams part"
              if ref_reqs is not None else "")
    print(f"[check] {label}: {total} tokens vs a full-sequence forward, "
          f"worst {worst:.4f} std below the top logit; {len(outliers)} "
          f"beyond {TIE_TOL} (allowed {outlier_share:.0%}){agreed}",
          flush=True)
    for o in outliers:
        print(f"[check] {label}: {o}", flush=True)
    if len(outliers) > outlier_share * total:
        fails.append(f"{label}: {len(outliers)} of {total} tokens more than "
                     f"{TIE_TOL} std below the top logit")
    return fails


def compare_pallas_matmul(model, cfg, params, prompt) -> list:
    """Prefill logits of one prompt: Pallas kernels vs the XLA matmul
    path on the same parameters."""
    matmul_cfg = dataclasses.replace(cfg, sell_method="matmul")

    @jax.jit
    def errors(params, tokens):
        a = model.apply(params, tokens, cfg)
        b = model.apply(params, tokens, matmul_cfg)
        rel = jnp.linalg.norm(a - b) / jnp.linalg.norm(b)
        agree = jnp.mean(jnp.argmax(a, -1) == jnp.argmax(b, -1))
        return rel, agree

    rel, agree = (float(v) for v in errors(
        params, jnp.asarray([prompt], jnp.int32)))
    print(f"[check] pallas vs matmul prefill logits ({len(prompt)} tokens): "
          f"relative L2 error {rel:.3e} (tol {PALLAS_TOL}), argmax "
          f"agreement {agree:.4f}", flush=True)
    if not rel <= PALLAS_TOL:
        return [f"pallas vs matmul logits: relative error {rel:.3e} > "
                f"{PALLAS_TOL}"]
    return []


def check_paged_kernel(cfg, args) -> list:
    """One decode step of the fused paged-attention kernel, with the block
    its route picks, at the served model's head shapes, page size, table
    length and pool dtype, against the gather oracle
    (``kernels/ref.paged_attention_ref``, fp32 at full precision).  The
    rows' new tokens land at the end of a page, at the start and inside
    the next one, and in the table's last slot."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_
    group, bs = cfg.n_heads // hkv, args.block_size
    mb = -(-(args.prompt_len + args.gen) // bs)
    mid = bs * (mb // 2)
    pos = jnp.asarray([mid - 1, mid, mid + 3, bs * mb - 1], jnp.int32)
    b, nb = len(pos), len(pos) * mb
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (b, 1, hkv * group, dh), dtype)
    knew, vnew = (jax.random.normal(k, (b, 1, hkv, dh), dtype)
                  for k in keys[1:3])
    kp, vp = (jax.random.normal(k, (nb + 1, bs, hkv, dh), dtype)
              for k in keys[3:])
    tbl = jnp.arange(nb, dtype=jnp.int32).reshape(b, mb)
    operands = (q, knew, vnew, kp, vp, tbl, pos, jnp.int32(0))
    blk = ops.paged_attn_route(hkv, dh, group, 1, bs, dtype)
    if blk is None:
        return [f"paged kernel check: route took the gather at {dtype}"]
    fo, fk, fv = jax.jit(functools.partial(
        paged_attn.paged_attention, softcap=cfg.attn_logit_softcap,
        page_chunk=blk[0], head_block=blk[1],
        interpret=ops.interpret_mode()))(*operands)
    ro, rk, rv = jax.jit(functools.partial(
        ref.paged_attention_ref, softcap=cfg.attn_logit_softcap))(*operands)
    fo, ro = (np.asarray(o, np.float32) for o in (fo, ro))
    rel = float(np.linalg.norm(fo - ro) / np.linalg.norm(ro))
    # every new token has a mapped page, so neither writes the trash page
    pools = bool(np.array_equal(np.asarray(fk), np.asarray(rk))
                 and np.array_equal(np.asarray(fv), np.asarray(rv)))
    print(f"[check] paged kernel ({dtype.name} pools, Hkv {hkv} x group "
          f"{group} x Dh {dh}, pages of {bs}, table {mb}, block {blk}): "
          f"relative L2 error {rel:.3e} (tol {KERNEL_TOL}); pools "
          f"{'bitwise equal' if pools else 'DIFFER'}", flush=True)
    fails = []
    if not rel <= KERNEL_TOL:
        fails.append(f"paged kernel: relative error {rel:.3e} > {KERNEL_TOL}")
    if not pools:
        fails.append("paged kernel: pools differ from the oracle's writes")
    return fails


def serve_run(model, cfg, params, argv, clock, label: str):
    """One ``run_engine`` call; returns its requests and failures."""
    args = serve.parse_args(argv)
    before = _routes()
    with phase(f"serve {label}", clock):
        eng, reqs = serve.run_engine(model, cfg, params, args,
                                     jax.random.PRNGKey(0))
    print(f"[serve] {label}: {eng.stats['tokens_out']} tokens out, "
          f"{eng.stats['prefill_dispatches']} prefills, "
          f"{eng.stats['decode_ticks']} decode ticks", flush=True)
    fails = check_finished(reqs, cfg.vocab_size, label)
    if args.paged:
        fails += check_route(before, label)
    return reqs, fails


def serve_phases(model_argv, serve_argv, clock) -> list:
    """ACDC on paged KV, judged by a full-sequence forward and against
    the XLA matmul path; the paged and contiguous engines compared in
    fp32; then dense on paged KV after the ACDC model is freed."""
    fails = []
    args = serve.parse_args(model_argv + ACDC + serve_argv + PAGED)
    length = args.prompt_len + args.gen
    with phase("init acdc", clock):
        cfg, model, params = serve.build_model(args, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    reqs, f = serve_run(model, cfg, params,
                        model_argv + ACDC + serve_argv + PAGED, clock,
                        "acdc paged")
    fails += f
    with phase("check acdc", clock):
        fails += check_streams(model, cfg, params, reqs, length,
                               label="acdc paged",
                               outlier_share=BF16_OUTLIER_SHARE)
        fails += compare_pallas_matmul(model, cfg, params, reqs[0].prompt)
        fails += check_paged_kernel(cfg, args)
    # Paged vs contiguous KV is a question of logic, not of rounding: at
    # random init and 28 layers, bf16 rounding differences between two
    # attention implementations grow until some greedy choices part by
    # whole standard deviations of the logit row.  So both engines run
    # the same model in fp32 with full-precision XLA matmuls.
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        reqs32, f = serve_run(model, cfg32, params,
                              model_argv + ACDC + serve_argv + PAGED, clock,
                              "acdc fp32 paged")
        fails += f
        ref32, f = serve_run(model, cfg32, params,
                             model_argv + ACDC + serve_argv, clock,
                             "acdc fp32 contiguous")
        fails += f
        with phase("check acdc fp32", clock):
            fails += check_streams(model, cfg32, params, reqs32, length,
                                   ref32, "acdc fp32 paged vs contiguous")
    del params
    gc.collect()

    args = serve.parse_args(model_argv + DENSE + serve_argv + PAGED)
    with phase("init dense", clock):
        cfg, model, params = serve.build_model(args, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    _, f = serve_run(model, cfg, params,
                     model_argv + DENSE + serve_argv + PAGED, clock,
                     "dense paged")
    return fails + f


def train_run(argv, clock, label: str):
    """One ``launch/train.main`` call; returns ``(losses, state)``."""
    with phase(f"train {label}", clock):
        losses, state = train.main(argv + [
            "--ckpt-every", "0", "--log-every", "1",
            "--ckpt-dir", str(cache_mod.CACHE_DIR / "smoke_ckpt")])
    print(f"[train] {label}: losses {losses}", flush=True)
    return losses, state


def train_phase(model_argv, train_argv, clock) -> list:
    print(f"[train] depth and batch: {' '.join(train_argv)} (all "
          f"layers of the model)", flush=True)
    before = _routes()
    losses, _ = train_run(model_argv + ACDC + train_argv, clock, "acdc")
    check_route(before, "train acdc")
    if not losses or not all(np.isfinite(losses)):
        return [f"train losses not finite: {losses}"]
    return []


def silence(params, live: int = LIVE_LAYERS):
    """Zero the last cascade layer's D in the attention-output and
    MLP-down SELL projections of every block from ``live`` on.  Those
    blocks then add nothing to the residual stream: the same program runs
    a network ``live`` blocks deep."""
    for block, proj in (("attn", "wo"), ("mlp", "wd")):
        sell = params["layers"][block][proj]["sell"]
        sell["d"] = sell["d"].at[live:, -1].set(0.0)
    return params


def perturb(params, rel: float = PERTURB):
    """Every weight times ``1 +- rel``, signs drawn from a fixed seed."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return jax.tree.unflatten(tree, [
        p * (1 + rel * jax.random.rademacher(k, p.shape, p.dtype))
        for p, k in zip(leaves, keys)])


def host_grads(state, grad_norm: float) -> dict:
    """The gradient of the one step that took ``state`` from zero moments,
    leaf by leaf on the host.  AdamW's first moment is then (1 - b1) times
    the clipped gradient, so one scalar, fixed by the step's
    ``grad_norm``, turns it back into the gradient."""
    m = state["opt"]["m"]
    paths = jax.tree.leaves(tree_paths(m))
    leaves = [np.asarray(x, np.float32) for x in jax.tree.leaves(m)]
    scale = grad_norm / np.sqrt(sum(np.linalg.norm(x) ** 2 for x in leaves))
    return {p: x * np.float32(scale) for p, x in zip(paths, leaves)}


def _norm(tree: dict) -> float:
    return float(np.sqrt(sum(np.linalg.norm(x) ** 2 for x in tree.values())))


def grad_gaps(ref: dict, other: dict) -> dict:
    """Relative L2 gap of ``other`` to ``ref``: in all, per leaf, per
    block of the stacked ``layers`` leaves, with each leaf's share of
    ``ref``'s norm."""
    diff = {p: other[p] - ref[p] for p in ref}
    total = _norm(ref)
    leaves = {p: (float(np.linalg.norm(ref[p]) / total),
                  float(np.linalg.norm(diff[p])
                        / max(np.linalg.norm(ref[p]), 1e-30)))
              for p in ref}
    stacked = [p for p in ref if p.startswith("layers/")]
    depth = ref[stacked[0]].shape[0] if stacked else 0
    blocks = [float(np.sqrt(sum(np.linalg.norm(diff[p][i]) ** 2
                                for p in stacked))
                    / max(np.sqrt(sum(np.linalg.norm(ref[p][i]) ** 2
                                      for p in stacked)), 1e-30))
              for i in range(depth)]
    return {"total": _norm(diff) / total, "norms": (total, _norm(other)),
            "leaves": leaves, "blocks": blocks}


def print_gaps(label: str, gaps: dict) -> None:
    worst = sorted(gaps["leaves"].items(), key=lambda kv: -kv[1][1])[:5]
    print(f"[grads] {label}: |g| {gaps['norms'][0]:.4f} vs "
          f"{gaps['norms'][1]:.4f}, relative gap {gaps['total']:.4e}; "
          f"worst leaves (share of |g|, gap) "
          + ", ".join(f"{p} ({sh:.3f}, {g:.3e})" for p, (sh, g) in worst)
          + "; per block " + " ".join(f"{g:.2e}" for g in gaps["blocks"]),
          flush=True)


def train_compare(args, mesh, clock, label: str,
                  spread: bool = False) -> dict:
    """The train steps of phase 1 on ``mesh`` through the launcher's
    ``build``; then single steps from a fresh state whose gradients come
    back leaf by leaf: batch 0 (the first step's), batch 1 (the second
    step's, whose weights the first step left unchanged: lr is 0 at step
    0), batch 1 on the silenced network, and with ``spread`` batch 1 at
    perturbed weights."""
    grads = {}
    with phase(f"train {label}", clock):
        cfg, model, opt, mesh, jitted, pipeline, state_sh, batch_sh = \
            train.build(args.arch, args.smoke, args.sell, args.seq_len,
                        args.global_batch, args.lr, args.steps, mesh=mesh,
                        sell_method=args.sell_method)
        print(f"[mesh] {label}: {dict(mesh.shape)} over {mesh.size} "
              f"device(s)", flush=True)
        with jax.set_mesh(mesh):
            def fresh(edit=None):
                state = steps_mod.init_state(model, cfg, opt,
                                             jax.random.PRNGKey(0))
                if edit is not None:
                    state["params"] = edit(state["params"])
                return jax.device_put(state, state_sh)

            def run(state, step):
                batch = jax.device_put(pipeline.batch_at(step), batch_sh)
                state, metrics = jitted(state, batch)
                return state, float(metrics["loss"]), float(
                    metrics["grad_norm"])

            state, losses, norms = fresh(), [], []
            for step in range(args.steps):
                state, loss, norm = run(state, step)
                losses.append(loss)
                norms.append(norm)
                if step == 0:
                    grads["batch 0"] = host_grads(state, norm)
            print(f"[train] {label}: losses {losses}; |g| {norms}",
                  flush=True)
            held = {d: 0 for d in jax.devices()}
            for leaf in jax.tree.leaves(state):
                for shard in leaf.addressable_shards:
                    held[shard.device] += shard.data.nbytes
            state_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
            del state
            runs = [("batch 1", None), ("silenced, batch 1", silence)]
            if spread:
                runs.append(("perturbed, batch 1", perturb))
            for name, edit in runs:
                state, loss, norm = run(fresh(edit), 1)
                grads[name] = host_grads(state, norm)
                print(f"[train] {label}, {name}: loss {loss}; |g| {norm}",
                      flush=True)
                del state
    return {"losses": losses, "norms": norms, "grads": grads,
            "held": list(held.values()), "state_bytes": state_bytes}


def four_chip_phase(model_argv, train_argv, clock) -> list:
    """The launcher's (data=2, model=2) mesh against one device."""
    args = train.parse_args(model_argv + ACDC + train_argv)
    mesh = train_compare(args, make_host_mesh(model_axis=2), clock,
                         "mesh data=2 model=2")
    in_use = [_memory_stats(d).get("bytes_in_use") for d in jax.devices()]
    print(f"[memory] train state {mesh['state_bytes']} bytes; held per "
          f"device {mesh['held']}; bytes_in_use per device {in_use}",
          flush=True)
    fails = []
    if max(mesh["held"]) >= mesh["state_bytes"] or not all(mesh["held"]):
        fails.append(f"train state not spread over the devices: "
                     f"{mesh['held']} of {mesh['state_bytes']} bytes")
    gc.collect()
    one = train_compare(args, make_host_mesh(n_devices=1), clock,
                        "one device", spread=True)

    diffs = [abs(a - b) for a, b in zip(mesh["losses"], one["losses"])]
    print(f"[check] per-step |loss(mesh) - loss(one device)| {diffs} "
          f"(tol {LOSS_TOL}); |g| mesh {mesh['norms']}, one device "
          f"{one['norms']}", flush=True)
    if len(mesh["losses"]) != len(one["losses"]) or not all(
            np.isfinite(mesh["losses"])) or max(diffs) > LOSS_TOL:
        fails.append(f"mesh losses {mesh['losses']} vs one device "
                     f"{one['losses']}")
    for name in ("batch 0", "batch 1"):
        print_gaps(f"mesh vs one device, {name}",
                   grad_gaps(one["grads"][name], mesh["grads"][name]))
    print_gaps(f"one device's rounding spread: weights x (1 +- {PERTURB}), "
               f"batch 1", grad_gaps(one["grads"]["batch 1"],
                                     one["grads"]["perturbed, batch 1"]))
    name = "silenced, batch 1"
    gaps = grad_gaps(one["grads"][name], mesh["grads"][name])
    print_gaps(f"mesh vs one device, {LIVE_LAYERS} live blocks, batch 1",
               gaps)
    # leaves below a thousandth of the norm are zero or nearly so (the
    # silenced blocks' weights upstream of the zeroed D)
    bad = {p: g for p, (share, g) in gaps["leaves"].items()
           if share >= 1e-3 and not g <= GRAD_TOL}
    if not gaps["total"] <= GRAD_TOL or bad:
        fails.append(f"silenced network: mesh gradient off one device's by "
                     f"{gaps['total']:.3e} in all, leaves {bad} (tol "
                     f"{GRAD_TOL})")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training path and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    cache_mod.configure_compile_cache()

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    dev = devices[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)} | "
          f"jax {jax.__version__}", flush=True)

    clock = CompileClock()
    if args.four_chips:
        fails = four_chip_phase(MODEL, TRAIN, clock)
    else:
        fails = serve_phases(MODEL, SERVE, clock)
        fails += train_phase(MODEL, TRAIN, clock)
    if fails:
        for f in fails:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
