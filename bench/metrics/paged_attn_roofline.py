"""Roofline share of the paged-attention kernel in the decode steps:
attention work by context length (``work/counts``) over the kernel's
device time."""

from _common import counts, decode_tokens, roofline
from harness.trace import kernel_seconds


def read(run):
    s, elem = run["sizes"], run["elem"]
    ctx = decode_tokens(run)
    flops = s["n_layers"] * sum(counts.attn_flops(s, c) for c in ctx)
    nbytes = s["n_layers"] * sum(counts.attn_bytes(s, c, elem) for c in ctx)
    return roofline(flops, nbytes, kernel_seconds(run["trace"], "decode",
                                                  "paged_attn"), run["peak"])
