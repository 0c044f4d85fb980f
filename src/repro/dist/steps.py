"""Train/serve step builders and state trees for the launch stack.

The functions here are pure closures over (model, cfg, opt) so the
launchers can wrap them in ``jax.jit`` with explicit in/out shardings
(see :mod:`repro.dist.sharding`) and the dry-run can ``.lower()`` them
against ShapeDtypeStructs without allocating anything.

State layout (a plain dict pytree, checkpoint- and eval_shape-friendly)::

    {"params": <model params>, "opt": <optimizer state>, "step": int32[]}

SELL routing note: the step builders are transform-family agnostic.  The
``sell_kind`` / ``sell_method`` / ``sell_transform`` trio lives entirely
inside ``cfg`` (models/common.py) and is consumed by
``models.linear._sell_cfg`` at trace time — a family swap changes the
traced computation (which ``C`` matrices the kernels receive, which
autotune cache line feeds ``bm``) but not the state tree's structure, the
shardings, or anything this module builds.  The SELL param-group LR
multipliers in launch/train.py key on param-tree paths (``sell/a`` etc.),
which are also family-invariant.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist import compression
from repro.optim.optimizers import global_norm, tree_add


# ---------------------------------------------------------------------------
# State trees.
# ---------------------------------------------------------------------------

def init_state(model, cfg, opt, rng: jax.Array, compress_dp: int = 0) -> dict:
    """Concrete train state: params + optimizer moments + step counter.

    ``compress_dp > 0`` adds a ``grad_error`` tree — the per-data-rank int8
    quantization residuals (leading axis = data-parallel size) carried by
    the compressed gradient sync (:mod:`repro.dist.compression`).
    """
    params = model.init(rng, cfg)
    state = {
        "params": params,
        "opt": opt.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if compress_dp > 0:
        state["grad_error"] = jax.tree.map(
            lambda p: jnp.zeros((compress_dp,) + p.shape, jnp.float32),
            params)
    return state


def abstract_state(model, cfg, opt, compress_dp: int = 0) -> dict:
    """ShapeDtypeStruct mirror of :func:`init_state` (no allocation)."""
    return jax.eval_shape(
        functools.partial(init_state, model, cfg, opt,
                          compress_dp=compress_dp),
        jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Training.
# ---------------------------------------------------------------------------

def make_train_step(model, cfg, opt, accum_steps: int = 1,
                    compress_mesh=None, data_axis: str = "data") -> Callable:
    """Build ``step(state, batch) -> (new_state, metrics)``.

    ``accum_steps > 1`` splits the global batch into equal microbatches and
    accumulates loss/grads with a ``lax.scan`` (live memory is one
    microbatch's activations; the compiled program is O(1) in the number of
    microbatches).  With equal token counts per microbatch the mean loss
    and mean grads match the full-batch computation exactly, which
    tests/test_train_integration.py pins down.

    ``compress_mesh`` (a Mesh) routes the data-parallel gradient all-reduce
    through :func:`repro.dist.compression.compressed_psum_tree` under
    ``shard_map`` over ``data_axis``: int8 on the wire with error feedback.
    The state must then carry a ``grad_error`` tree (``init_state`` with
    ``compress_dp = mesh.shape[data_axis]``).  This path treats params as
    replicated across ``data_axis`` inside the shard_map body (pure data
    parallelism — the inter-pod DP sync is the traffic worth compressing);
    model-parallel placement still applies outside via jit shardings.

    With ``cfg.sell_method='pallas'`` the SELL projections' cascades
    differentiate through the fused cascade custom VJP, whose backward is
    the reverse-sweep Pallas kernel (``kernels/acdc_cascade_bwd``) — the
    train step's gradient pass moves O(N) HBM bytes per row regardless
    of cascade depth, matching the fused forward.  No step-builder
    plumbing is involved; ``jax.value_and_grad`` picks the VJP up here,
    which tests/test_kernel_grads.py pins with a routing assertion.
    """
    def loss_fn(params, batch):
        return model.loss_fn(params, batch, cfg)

    def grads_of(params, batch):
        if accum_steps <= 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def split(x):
            b = x.shape[0]
            if b % accum_steps:
                raise ValueError(
                    f"global batch {b} not divisible by accum {accum_steps}")
            return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

        micro = jax.tree.map(split, batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)

        def body(carry, mb):
            loss_acc, grad_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            grad_acc = jax.tree.map(
                lambda a, b_: a + b_.astype(jnp.float32), grad_acc, g)
            return (loss_acc + l, grad_acc), None

        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / accum_steps
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def compressed_grads_of(params, batch, error):
        """Per-rank grads + error-feedback int8 psum under shard_map."""
        dsize = compress_mesh.shape[data_axis]

        def local_fn(params, batch, error):
            loss, grads = grads_of(params, batch)
            err = jax.tree.map(lambda e: e[0], error)       # drop rank axis
            grads, new_err = compression.compressed_psum_tree(
                grads, err, data_axis)
            grads = jax.tree.map(lambda g: g / dsize, grads)  # psum -> mean
            loss = jax.lax.pmean(loss, data_axis)
            return loss, grads, jax.tree.map(lambda e: e[None], new_err)

        rep = jax.tree.map(lambda _: P(), params)
        sharded = jax.tree.map(lambda _: P(data_axis), batch)
        err_spec = jax.tree.map(lambda _: P(data_axis), error)
        return shard_map(
            local_fn, mesh=compress_mesh,
            in_specs=(rep, sharded, err_spec),
            out_specs=(P(), rep, err_spec),
            check_vma=False,
        )(params, batch, error)

    def step(state, batch):
        if compress_mesh is not None:
            loss, grads, new_error = compressed_grads_of(
                state["params"], batch, state["grad_error"])
        else:
            loss, grads = grads_of(state["params"], batch)
            new_error = None
        updates, new_opt = opt.update(grads, state["opt"], state["params"],
                                      state["step"])
        new_params = tree_add(state["params"], updates)
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": global_norm(grads),
            "update_norm": global_norm(updates),
        }
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if new_error is not None:
            new_state["grad_error"] = new_error
        return new_state, metrics

    return step


# ---------------------------------------------------------------------------
# Serving (single-token decode against the model-zoo caches).
# ---------------------------------------------------------------------------

def make_serve_step(model, cfg, sample: str = "greedy",
                    temperature: float = 1.0, top_k: int = 0,
                    top_p: float = 0.0, paged: bool = False) -> Callable:
    """Build ``step(params, cache, tokens, position, rng) -> (next, cache)``.

    One decode step against the family-specific cache (KV for attention
    archs, recurrent SSM/conv state for mamba-style archs, both for the
    hybrid) followed by on-device sampling: ``greedy`` argmax or ``temp``
    temperature-scaled categorical with optional top-k / top-p filtering
    (:mod:`repro.serving.sampler`).

    ``paged=True`` decodes against the paged block KV cache instead; the
    step signature gains the per-slot block tables:
    ``step(params, cache, tokens, position, block_tables, rng)``.  Inside
    the traced program, paged attention routes per ``ops.paged_attn_route``
    — the fused streaming kernel (``kernels/paged_attn.py``) on TPU when a
    block fits VMEM, the block-table gather otherwise — with identical
    greedy streams either way.
    """
    from repro.serving import sampler as sampler_mod  # avoid import cycle

    if sample not in ("greedy", "temp"):
        raise ValueError(f"unknown sampler {sample!r}")

    if paged:
        if model.decode_step_paged is None:
            raise ValueError(
                f"family {cfg.family!r} has no paged decode path")

        def step(params, cache, tokens, position, block_tables, rng):
            logits, new_cache = model.decode_step_paged(
                params, cache, tokens, position, block_tables, cfg)
            nxt = sampler_mod.sample(rng, logits, method=sample,
                                     temperature=temperature, top_k=top_k,
                                     top_p=top_p)
            return nxt, new_cache

        return step

    def step(params, cache, tokens, position, rng):
        logits, new_cache = model.decode_step(params, cache, tokens,
                                              position, cfg)
        nxt = sampler_mod.sample(rng, logits, method=sample,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)
        return nxt, new_cache

    return step


def make_insert_step() -> Callable:
    """jit'd slot insert: write a batch-1 slot cache into batch row
    ``slot`` of the full decode cache (donated — it is the dominant
    serving allocation and is replaced wholesale, so XLA updates the
    buffers in place).  Shared by the dense engine's admission path and
    the speculative draft's slot cache."""

    def insert(cache, slot_cache, slot):
        return jax.tree.map(
            lambda c, s: jax.lax.dynamic_update_slice_in_dim(
                c, s.astype(c.dtype), slot, axis=1),
            cache, slot_cache)

    return jax.jit(insert, donate_argnums=(0,))


def make_verify_step(model, cfg, sample: str = "greedy",
                     temperature: float = 1.0, top_k: int = 0,
                     top_p: float = 0.0, paged: bool = False,
                     park: Optional[int] = None) -> Callable:
    """Build the speculative-decode verify step — ONE lowered program that
    appends k+1 tokens per slot, scores them, accepts, and commits.

    ``step(params, cache, tokens (B, k+1), drafts (B, k), draft_logits
    (B, k, V), position (B,)[, block_tables], rng) ->
    (accepted (B,), out_tokens (B, k+1), new_cache)``

    ``tokens`` is ``[pending, d_1 .. d_k]`` per row; the model's
    ``verify_step`` scores every position against the cache (a
    cache-extending, position-masked mini-prefill), acceptance is
    exact-match (greedy) or rejection sampling (temp,
    :mod:`repro.spec.verify`), and the cache is committed in-program:
    KV leaves keep their set-writes (rejected tail positions sit beyond
    the rewound frontier), recurrent SSM/conv leaves are re-selected at
    each row's accepted length from the per-position snapshots.
    ``out_tokens[:, :n+1]`` is the committed stream (accepted drafts plus
    the correction/bonus token at index n).

    ``park`` is the engine's parked-row position sentinel (rows at or
    beyond it — free or stalled slots — commit zero tokens); ``None``
    treats every row as advancing.

    ``paged=True`` verifies against the paged pool through the same
    attention dispatch as the decode step: the fused paged-attention
    kernel handles the k+1-query verify grid natively (one kernel body
    for both T=1 and T=k+1), so speculative serving streams pages without
    ever materialising the gathered virtual rows.
    """
    from repro.spec import verify as verify_mod  # avoid import cycle

    if sample not in ("greedy", "temp"):
        raise ValueError(f"unknown sampler {sample!r}")
    vfn = model.verify_step_paged if paged else model.verify_step
    if vfn is None:
        raise ValueError(
            f"family {cfg.family!r} has no "
            f"{'paged ' if paged else ''}speculative verify path")

    def _accept_commit(logits, states, cache, drafts, draft_logits,
                       position, rng):
        if sample == "greedy":
            n, nxt = verify_mod.greedy_accept(logits, drafts)
        else:
            n, nxt = verify_mod.rejection_accept(
                rng, logits, draft_logits, drafts, temperature=temperature,
                top_k=top_k, top_p=top_p)
        out = verify_mod.committed_tokens(drafts, n, nxt)
        if states is not None:
            advancing = (position < park) if park is not None else True
            n_adv = jnp.where(advancing, n + 1, 0).astype(jnp.int32)
            cache = verify_mod.commit_states(cache, states, n_adv)
        return n, out, cache

    if paged:
        def step(params, cache, tokens, drafts, draft_logits, position,
                 block_tables, rng):
            logits, new_cache, states = vfn(params, cache, tokens, position,
                                            block_tables, cfg)
            return _accept_commit(logits, states, new_cache, drafts,
                                  draft_logits, position, rng)

        return step

    def step(params, cache, tokens, drafts, draft_logits, position, rng):
        logits, new_cache, states = vfn(params, cache, tokens, position, cfg)
        return _accept_commit(logits, states, new_cache, drafts,
                              draft_logits, position, rng)

    return step


def make_prefill_step(model, cfg, full_logits: bool = False,
                      paged: bool = False) -> Callable:
    """Build ``step(params, cache, tokens, lengths[, fe]) -> (logits, cache)``.

    One lowered program runs the model over the whole (right-padded) prompt
    batch and scatters the resulting KV / SSM state into the decode cache —
    replacing ``prompt_len`` sequential decode dispatches with a single
    compiled prefill (the ROADMAP batched-prefill item).  ``lengths`` (B,)
    gives each row's real prompt length; cache slots at or beyond it are
    zeroed so the additive decode scatter stays sound when continuous
    batching reuses slots.

    Returns the logits at each row's last real token (B, V) by default, or
    the full (B, S, V) grid with ``full_logits=True`` (equivalence tests,
    dry-run lowering).

    ``paged=True`` builds the admission program for the paged engine
    instead: ``step(params, cache, template, tokens, lengths, phys_blocks,
    slot[, fe]) -> (last_logits, cache)``.  The batch-1 prefill runs into
    the dense ``template`` slab, whose KV is then page-scattered through
    ``phys_blocks`` (the slot's block-table row, unmapped entries already
    routed to the trash page) while batch-indexed leaves (encdec cross KV,
    zamba2 SSM/conv state) slot-insert at ``slot`` — prefill and the paged
    cache scatter stay ONE lowered program per admission.
    """
    if model.prefill is None:
        raise ValueError(f"family {cfg.family!r} has no prefill path")

    if paged:
        if model.init_cache_paged is None:
            raise ValueError(
                f"family {cfg.family!r} has no paged cache")
        from repro.models import attention as attn_mod

        def step(params, cache, template, tokens, lengths, phys_blocks,
                 slot, frontend_embeds=None):
            logits, slot_cache = model.prefill(params, template, tokens,
                                               cfg, lengths, frontend_embeds)
            new_cache = {}
            for key, leaf in cache.items():
                if key.endswith("_pages"):
                    slab = slot_cache[key[: -len("_pages")]]
                    new_cache[key] = attn_mod.scatter_prefill_pages(
                        leaf, slab, phys_blocks)
                else:
                    new_cache[key] = jax.lax.dynamic_update_slice_in_dim(
                        leaf, slot_cache[key].astype(leaf.dtype), slot,
                        axis=1)
            idx = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(logits, idx[:, None, None],
                                       axis=1)[:, 0]
            return last, new_cache

        return step

    def step(params, cache, tokens, lengths, frontend_embeds=None):
        logits, new_cache = model.prefill(params, cache, tokens, cfg,
                                          lengths, frontend_embeds)
        if full_logits:
            return logits, new_cache
        idx = jnp.maximum(lengths - 1, 0)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        return last, new_cache

    return step
