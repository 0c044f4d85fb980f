"""Time every paged-attention block at the serving cells' decode shapes
and write the winner that the benchmark pins for this device kind.

    python3 bench/tune.py --out bench/autotune/<device_kind>.json

The program picks the (page_chunk, head_block) of its paged-attention
kernel by a short timing at small representative sizes the first time it
runs in a checkout, and keeps the winner there; two checkouts of one tree
can then run different kernels.  ``run.py`` gives the program the
winners in ``bench/autotune/<device_kind>.json`` instead, so that every
checkout runs the same one.  This script makes that file: for each
serving cell, a full batch of slots decodes one token each against the
cell's own pool, at contexts drawn from the cell's mix, with each legal
candidate, 1000 kernel calls in one jitted loop, five times; the winner
has the least sum over the cells of its median time relative to the
cell's fastest.  ``--rehearse`` runs it on the CPU at smoke sizes.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def cell_shape(cell, rehearse: bool) -> dict:
    from harness import program, serve_loop

    shape = serve_loop._engine_shape(cell.config, rehearse)
    cfg, _, _ = program.serve_model(cell.config, rehearse)
    max_len = shape["max_prompt_len"] + shape["max_new_tokens"] + 1
    return {"rows": shape["slots"], "bs": shape["block_size"],
            "pool": shape["n_blocks"], "mb": -(-max_len // shape["block_size"]),
            "hq": cfg.n_heads, "hkv": cfg.n_kv_heads, "dh": cfg.head_dim_,
            "mix": serve_loop._clip_mix(cell.mix, shape) if rehearse
            else cell.mix}


def operands(s: dict, dtype):
    """A full batch mid-stream: row i decodes at a prompt from the mix plus
    half its output, its pages scattered over the pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import traffic

    rng = np.random.default_rng(0)
    prompt = traffic.lengths(s["mix"]["prompt"], s["rows"], rng)
    out = traffic.lengths(s["mix"]["output"], s["rows"], rng)
    pos = np.minimum(prompt + out // 2, s["mb"] * s["bs"] - 1)
    pages = rng.permutation(s["pool"])
    tbl = np.full((s["rows"], s["mb"]), -1, np.int32)
    used = 0
    for i, p in enumerate(pos):
        n = int(p) // s["bs"] + 1
        tbl[i, :n] = pages[used:used + n]
        used += n
    assert used <= s["pool"], "the batch needs more pages than the pool"
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (s["rows"], 1, s["hq"], s["dh"]), dtype)
    kn = jax.random.normal(jax.random.fold_in(key, 1),
                           (s["rows"], 1, s["hkv"], s["dh"]), dtype)
    vn = jax.random.normal(jax.random.fold_in(key, 2),
                           (s["rows"], 1, s["hkv"], s["dh"]), dtype)
    kp = jax.random.normal(jax.random.fold_in(key, 3),
                           (s["pool"] + 1, s["bs"], s["hkv"], s["dh"]), dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 4),
                           (s["pool"] + 1, s["bs"], s["hkv"], s["dh"]), dtype)
    return (q, kn, vn, kp, vp, jnp.asarray(tbl),
            jnp.asarray(pos, jnp.int32), jnp.int32(0))


def candidates(s: dict, itemsize: int):
    from repro.kernels import paged_attn as pa

    return [(pc, bh) for pc in pa.PAGE_CHUNKS for bh in pa.HEAD_BLOCKS
            if pa.legal_head_block(bh, s["hkv"])
            and pa.paged_attn_vmem_bytes(
                bs=s["bs"], dh=s["dh"], group=s["hq"] // s["hkv"], t=1,
                pc=pc, bh=bh, itemsize=itemsize) <= pa.VMEM_BUDGET]


def time_block(args, blk, iters: int, reps: int, interpret: bool) -> float:
    """Median seconds per kernel call over ``reps`` loops of ``iters``."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import paged_attn as pa

    q, kn, vn, kp, vp, tbl, pos, win = args
    kernel = functools.partial(pa.paged_attention, softcap=0.0,
                               page_chunk=blk[0], head_block=blk[1],
                               interpret=interpret)

    @jax.jit
    def loop(kp, vp):
        def body(_, carry):
            kp, vp, acc = carry
            out, kp, vp = kernel(q, kn, vn, kp, vp, tbl, pos, win)
            return kp, vp, acc + out[0, 0, 0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, iters, body, (kp, vp, jnp.float32(0)))

    kp, vp, _ = jax.block_until_ready(loop(kp, vp))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        kp, vp, _ = jax.block_until_ready(loop(kp, vp))
        times.append((time.perf_counter() - t0) / iters)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import jax
    import jax.numpy as jnp

    from harness import program, spec  # noqa: F401 (puts src on the path)
    from repro.kernels import autotune
    from repro.kernels import paged_attn as pa

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    dtype = jnp.bfloat16
    iters, reps = (2, 1) if args.rehearse else (1000, 5)
    ratios, times, key = {}, {}, None
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], BENCH)
        if cell.mix["kind"] != "serve":
            continue
        s = cell_shape(cell, args.rehearse)
        # the program's own cache key for this call site
        k = autotune._key_str(("paged_attn", s["dh"], 1, jnp.dtype(dtype).name,
                               False, False, "acdc"))
        if key not in (None, k):
            raise SystemExit(f"cells differ in their key: {key} and {k}")
        key = k
        ops = operands(s, dtype)
        t = {f"{pc}x{bh}": time_block(ops, (pc, bh), iters, reps,
                                      interpret=args.rehearse)
             for pc, bh in candidates(s, jnp.dtype(dtype).itemsize)}
        best = min(t.values())
        times[w["name"]] = {b: v * 1e3 for b, v in t.items()}
        for b, v in t.items():
            ratios[b] = ratios.get(b, 0.0) + v / best
        print(f"[tune] {w['name']}: ms per call {times[w['name']]}",
              file=sys.stderr, flush=True)
    win = min(ratios, key=ratios.get)
    pc, bh = (int(x) for x in win.split("x"))
    dev = jax.devices()[0]
    blob = {"device_kind": dev.device_kind, "backend": jax.default_backend(),
            "entries": {key: pa.encode_block((pc, bh))},
            "winner": win, "ms_per_call": times,
            "how": "bench/tune.py: 1000 calls in one jitted loop, median of "
                   "5, at each serving cell's full batch, pool and mix"}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(blob, indent=1) + "\n")
    print(json.dumps(blob))
    return 0


if __name__ == "__main__":
    sys.exit(main())
