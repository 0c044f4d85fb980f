"""Production training launcher.

Wires together: config -> model -> optimizer (paper lr-multiplier groups
for SELL diagonals) -> sharded train state -> pjit train step -> data
pipeline -> checkpoint manager (async, atomic, keep-k) -> elastic policy
(SIGTERM drain + straggler monitor).

Runs for real on whatever devices exist (CPU in this container, a pod on
the cluster — the same code path; only the mesh shape changes).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_1_7b --smoke \
        --steps 20 --sell acdc
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import cache as cache_mod
from repro.checkpoint import CheckpointManager
from repro.configs import registry
from repro.data import DataConfig, SyntheticLM
from repro.dist import compression, elastic, sharding as shard_mod, \
    steps as steps_mod
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.obs import REGISTRY, JsonlExporter
from repro.optim import (OptimizerConfig, cosine_schedule, make_optimizer,
                         tree_paths)

# The paper's per-group treatment of the SELL diagonals (section 6.2):
# lr x24 on A, x12 on D, no weight decay on either; norms/bias undecayed.
SELL_GROUPS = (
    (r"sell/a$", {"lr_mult": 24.0, "weight_decay": 0.0}),
    (r"sell/d$", {"lr_mult": 12.0, "weight_decay": 0.0}),
    (r"sell/", {"weight_decay": 0.0}),
    (r"norm|scale$|bias$", {"weight_decay": 0.0}),
)


def build(arch: str, smoke: bool, sell: str, seq_len: int,
          global_batch: int, lr: float, total_steps: int,
          accum_steps: int = 1, mesh=None, compress_grads: bool = False,
          sell_method: str = "auto", sell_transform: str = "acdc"):
    cfg = registry.get_smoke_config(arch) if smoke else registry.get_config(arch)
    cfg = registry.with_sell(cfg, sell, method=sell_method,
                             transform=sell_transform)
    model = get_model(cfg)
    opt = make_optimizer(
        OptimizerConfig(kind="adamw", lr=lr, groups=SELL_GROUPS),
        cosine_schedule(lr, max(total_steps // 20, 1), total_steps))
    mesh = mesh or make_host_mesh()
    if compress_grads and dict(mesh.shape).get("model", 1) > 1:
        # the compressed shard_map treats params as replicated across the
        # whole mesh; on a model-parallel mesh that would silently
        # all-gather the full param tree onto every device
        raise ValueError("--compress-grads supports data-parallel meshes "
                         "only (model axis must be 1)")
    compress_dp = dict(mesh.shape)["data"] if compress_grads else 0
    train_step = steps_mod.make_train_step(
        model, cfg, opt, accum_steps,
        compress_mesh=mesh if compress_grads else None)

    state_abs = steps_mod.abstract_state(model, cfg, opt,
                                         compress_dp=compress_dp)
    state_sh = shard_mod.param_shardings(state_abs, mesh)
    if compress_grads:
        # per-rank residuals live on their rank: leading axis over "data"
        state_sh["grad_error"] = jax.tree.map(
            lambda _: NamedSharding(mesh, P("data")),
            state_abs["grad_error"])

    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size,
        seq_len=seq_len,
        global_batch=global_batch,
        frontend=cfg.frontend,
        n_frontend_tokens=(cfg.n_frontend_tokens
                           or (seq_len // 4 if cfg.frontend == "audio" else 0)),
        d_model=cfg.d_model,
    )
    pipeline = SyntheticLM(data_cfg)
    batch_abs = jax.eval_shape(pipeline.batch_at, 0)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            shard_mod.data_specs(mesh, batch_abs))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    metrics_sh = {"loss": rep, "grad_norm": rep, "update_norm": rep}

    jitted = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metrics_sh),
                     donate_argnums=(0,))
    return cfg, model, opt, mesh, jitted, pipeline, state_sh, batch_sh


def _train_metrics():
    """Training diagnostics in the process-global registry (names are
    documented in the ``repro/obs/__init__.py`` glossary)."""
    return {
        "loss": REGISTRY.gauge("train_step_loss", "last step loss"),
        "tps": REGISTRY.gauge("train_tokens_per_s",
                              "last step token throughput"),
        "step_s": REGISTRY.histogram("train_step_seconds",
                                     "step wall time (incl. compile on "
                                     "the first step)"),
        "wire": REGISTRY.gauge("train_grad_compressed_bytes",
                               "int8+scales gradient wire bytes per "
                               "all-reduce"),
        "raw": REGISTRY.gauge("train_grad_raw_bytes",
                              "fp32-equivalent gradient bytes per "
                              "all-reduce"),
        "diag": REGISTRY.gauge("train_cascade_diag_norm",
                               "per-cascade SELL diagonal l2 norm",
                               labels=("param", "cascade")),
    }


def _grad_wire_bytes(params):
    """Static per-all-reduce payload of the int8 blockwise compressor
    (int8 payload padded to BLOCK plus one fp32 scale per block) vs the
    uncompressed fp32 equivalent."""
    wire = raw = 0
    for leaf in jax.tree.leaves(params):
        n = max(int(np.prod(leaf.shape)), 1)
        nb = -(-n // compression.BLOCK)
        wire += nb * compression.BLOCK + 4 * nb
        raw += 4 * n
    return wire, raw


def _emit_diag_norms(gauge, params) -> None:
    """Per-cascade ||A||_2 / ||D||_2 gauges — the paper's init/depth
    sensitivity lives in how these diagonals move, so expose them per
    cascade (labeled by the param path) rather than as one global norm."""
    paths = jax.tree.leaves(tree_paths(params))
    for path, leaf in zip(paths, jax.tree.leaves(params)):
        for suffix in ("a", "d"):
            if path.endswith(f"sell/{suffix}"):
                cascade = path[: -len(f"/sell/{suffix}")]
                gauge.labels(param=suffix, cascade=cascade).set(
                    float(np.linalg.norm(np.asarray(leaf))))


def _restore(ckpt, step, model, cfg, opt, compress_dp, state_sh):
    """Elastic-safe restore: grad_error residuals are an optimization, not
    model state, so a checkpoint that lacks them (compression turned on
    after the save) or carries them for a different data-parallel size
    (elastic shrink/grow changed the rank axis) restores everything else
    and re-zeros the residuals instead of silently mis-sharding them."""
    state_abs = steps_mod.abstract_state(model, cfg, opt,
                                         compress_dp=compress_dp)
    try:
        state = ckpt.restore(step, state_abs, state_sh)
    except KeyError:
        if not compress_dp:
            raise
        base_abs = {k: v for k, v in state_abs.items() if k != "grad_error"}
        base_sh = {k: v for k, v in state_sh.items() if k != "grad_error"}
        state = ckpt.restore(step, base_abs, base_sh)
        state["grad_error"] = None
    if compress_dp:
        err = state.get("grad_error")
        lead = (jax.tree.leaves(err)[0].shape[0] if err is not None else None)
        if lead != compress_dp:
            print(f"[compress] residual rank axis {lead} -> {compress_dp}: "
                  f"resetting error feedback", flush=True)
            fresh = jax.tree.map(
                lambda p: jnp.zeros((compress_dp,) + tuple(p.shape),
                                    jnp.float32), state["params"])
            state["grad_error"] = jax.device_put(fresh,
                                                 state_sh["grad_error"])
    return state


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b", choices=registry.ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--sell", default="dense")
    ap.add_argument("--sell-method", default="auto",
                    choices=["auto", "fft", "matmul", "pallas"],
                    help="transform backend for SELL projections; "
                         "'pallas' runs the fused whole-cascade kernel "
                         "(interpret mode off-TPU)")
    ap.add_argument("--sell-transform", default="acdc",
                    help="transform family for --sell acdc cascades "
                         "(core/families.py: acdc | circulant | hadamard)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25,
                    help="steps between checkpoints; 0 saves none, not "
                         "even the final one")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="append registry snapshots (JSON lines) to PATH "
                         "on the --log-every cadence; off when unset")
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 error-feedback gradient all-reduce "
                         "(repro.dist.compression) over the data axis")
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="resolve the mesh via ElasticPolicy from however "
                         "many devices survived (elastic restart drill); "
                         "0 = plain host mesh")
    return ap.parse_args(argv)


def main(argv=None):
    """Train for ``--steps`` steps; returns ``(losses, state)``: the loss
    of every step this run took and the final train state."""
    args = parse_args(argv)
    cache_mod.configure_compile_cache()

    mesh = None
    if args.model_parallel > 0:
        pol = elastic.ElasticPolicy(model_parallel=args.model_parallel)
        dshape = pol.resolve_mesh(len(jax.devices()))
        mesh = make_host_mesh(dshape[1], n_devices=dshape[0] * dshape[1])
        print(f"[elastic] resolved mesh data={dshape[0]} model={dshape[1]} "
              f"from {len(jax.devices())} devices", flush=True)

    cfg, model, opt, mesh, jitted, pipeline, state_sh, batch_sh = build(
        args.arch, args.smoke, args.sell, args.seq_len, args.global_batch,
        args.lr, args.steps, args.accum_steps, mesh=mesh,
        compress_grads=args.compress_grads, sell_method=args.sell_method,
        sell_transform=args.sell_transform)
    compress_dp = dict(mesh.shape)["data"] if args.compress_grads else 0

    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    hb = elastic.Heartbeat().install()
    monitor = elastic.StragglerMonitor()
    obs = _train_metrics()
    exporter = (JsonlExporter(args.metrics_jsonl, REGISTRY,
                              every=args.log_every, clock=time.time)
                if args.metrics_jsonl else None)

    with jax.set_mesh(mesh):
        start_step = 0
        if args.resume and ckpt.latest_step() is not None:
            latest = ckpt.latest_step()
            state = _restore(ckpt, latest, model, cfg, opt, compress_dp,
                             state_sh)
            start_step = int(latest)
            print(f"resumed from step {start_step} (elastic restore onto "
                  f"{dict(mesh.shape)})", flush=True)
        else:
            state = steps_mod.init_state(model, cfg, opt,
                                         jax.random.PRNGKey(0),
                                         compress_dp=compress_dp)
            state = jax.device_put(state, state_sh)

        if args.compress_grads:
            wire, raw = _grad_wire_bytes(state["params"])
            obs["wire"].set(wire)
            obs["raw"].set(raw)
            print(f"[compress] grad wire bytes {wire} vs fp32 {raw} "
                  f"({wire / max(raw, 1):.3f}x)", flush=True)

        losses = []
        for step in range(start_step, args.steps):
            t0 = time.time()
            batch = jax.device_put(pipeline.batch_at(step), batch_sh)
            state, metrics = jitted(state, batch)
            # sync before timing: dispatch is async, so the unblocked wall
            # time is just the enqueue cost (~ms) — the straggler monitor
            # would seed its EWMA from that and flag every real measurement
            jax.block_until_ready(metrics)
            dt = time.time() - t0
            losses.append(float(metrics["loss"]))
            obs["loss"].set(losses[-1])
            obs["tps"].set(args.global_batch * args.seq_len / max(dt, 1e-9))
            obs["step_s"].observe(dt)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                gn = float(metrics["grad_norm"])
                print(f"step {step:5d} loss {loss:.4f} |g| {gn:.3f} "
                      f"{dt*1e3:.0f}ms", flush=True)
                _emit_diag_norms(obs["diag"], state["params"])
                if exporter is not None:
                    exporter.export(step)
            # the first step's wall time is dominated by jit compilation —
            # seeding the EWMA with it would mask real stragglers for the
            # first dozens of steps (also after every resume/recompile)
            if step > start_step and monitor.observe(step, dt):
                print(f"[straggler] step {step} exceeded "
                      f"{monitor.factor}x EWMA", flush=True)
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                ckpt.save_async(step + 1, state, extra={"arch": args.arch})
            if hb.should_stop:
                print("[preempt] SIGTERM received: draining + checkpointing")
                ckpt.wait()
                ckpt.save(step + 1, state, extra={"arch": args.arch})
                break
        else:
            # completed (no preempt break): the final save must not run on
            # the drain path — it would mislabel a mid-run state as
            # ``args.steps`` and a resumed job would think training is done.
            ckpt.wait()
            if args.ckpt_every:
                ckpt.save(args.steps, state, extra={"arch": args.arch})
    if exporter is not None:
        exporter.close()
        print(f"[obs] metrics jsonl -> {args.metrics_jsonl} "
              f"({exporter.exports} snapshots)", flush=True)
    print("done.")
    return losses, state


if __name__ == "__main__":
    main()
