"""The work a layer's mathematics requires: operations and bytes.

These counts do not depend on the kernel that does the work, so that a
faster kernel raises its share and never its count:

* a dense projection ``in -> out`` is ``2 * in * out`` operations per row;
* an ACDC cascade of K layers at size N (input zero-padded to N) is, per
  layer, two length-N DCTs at ``2.5 * N * log2(N)`` each plus the two
  diagonal scalings (``2N``), and a bias-on-D (``N``) where there is one;
  the N x N transform matrix that today's kernel streams is in no count;
* bytes are the activations in and out plus the parameters, each element
  at the compute type's width (2 bytes for bfloat16): the least any
  implementation serving that type must move;
* attention is counted by context length: a query at context ``c`` costs
  ``4 * c * Hq * Dh`` operations (scores and the weighted sum) and reads
  ``2 * c * Hkv * Dh`` cached elements;
* the LM head is counted only for rows whose logits are used.

``sizes`` is the configuration file's ``model`` block
(``harness.program.sizes``), keyed by ``ModelConfig``'s field names.
"""

from __future__ import annotations

import math

def op_size(n_in: int, n_out: int) -> int:
    """ACDC's operating size: ``max(in, out)`` rounded up to 128."""
    return -(-max(n_in, n_out) // 128) * 128


def projections(sizes: dict):
    """Every projection of one block: ``(role, in, out, sell)``; the
    attention output and the three MLP projections are SELL targets."""
    d, f = sizes["d_model"], sizes["d_ff"]
    hq = sizes["n_heads"] * sizes["head_dim"]
    hk = sizes["n_kv_heads"] * sizes["head_dim"]
    sell = sizes["sell_kind"] == "acdc"
    return [("wq", d, hq, False), ("wk", d, hk, False), ("wv", d, hk, False),
            ("wo", hq, d, sell), ("wg", d, f, sell), ("wu", d, f, sell),
            ("wd", f, d, sell)]


def proj_flops(n_in: int, n_out: int, sell: bool, k: int = 2,
               bias: bool = False) -> float:
    """Operations per row of one projection."""
    if not sell:
        return 2.0 * n_in * n_out
    n = op_size(n_in, n_out)
    per = 2 * 2.5 * n * math.log2(n) + 2 * n + (n if bias else 0)
    return k * per


def proj_params(n_in: int, n_out: int, sell: bool, k: int = 2) -> int:
    return 2 * k * op_size(n_in, n_out) if sell else n_in * n_out


def proj_bytes(n_in: int, n_out: int, sell: bool, rows: int, elem: int,
               k: int = 2) -> float:
    """Bytes one call over ``rows`` rows must move."""
    return elem * (rows * (n_in + n_out) + proj_params(n_in, n_out, sell, k))


def sell_work(sizes: dict, rows: int, calls: int, elem: int):
    """``(flops, bytes)`` of every SELL projection of every layer over
    ``rows`` rows in ``calls`` program calls (each call reads the
    parameters once)."""
    flops = bts = 0.0
    for _, n_in, n_out, sell in projections(sizes):
        if not sell:
            continue
        flops += rows * proj_flops(n_in, n_out, True)
        bts += (proj_bytes(n_in, n_out, True, rows, elem)
                + (calls - 1) * elem * proj_params(n_in, n_out, True))
    return flops * sizes["n_layers"], bts * sizes["n_layers"]


def attn_flops(sizes: dict, context: int) -> float:
    """One query token at context ``context``, one layer."""
    return 4.0 * context * sizes["n_heads"] * sizes["head_dim"]


def attn_bytes(sizes: dict, context: int, elem: int) -> float:
    """Cached keys and values read, the new ones written, the query in and
    the output out: one token, one layer."""
    hk = sizes["n_kv_heads"] * sizes["head_dim"]
    hq = sizes["n_heads"] * sizes["head_dim"]
    return elem * (2 * context * hk + 2 * hk + 2 * hq)


def token_flops(sizes: dict, context: int) -> float:
    """Forward operations of one decoded token at context ``context``:
    every projection, attention and the head (its logits are sampled)."""
    per_layer = sum(proj_flops(i, o, s) for _, i, o, s in projections(sizes))
    per_layer += attn_flops(sizes, context)
    return (sizes["n_layers"] * per_layer
            + 2.0 * sizes["d_model"] * sizes["vocab_size"])
