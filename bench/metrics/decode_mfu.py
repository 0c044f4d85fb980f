"""Model operations of the traced decode steps (every projection,
attention by context, the head for each decoded row) over their device
time at the chip's peak."""

from _common import counts, decode_tokens


def read(run):
    calls = run["trace"]["programs"].get("decode", [])
    if not calls:
        return None
    flops = sum(counts.token_flops(run["sizes"], c)
                for c in decode_tokens(run))
    return 100.0 * flops / (sum(calls) * run["peak"]["flops_bf16"])
