"""Roofline share of the SELL kernels in the decode steps: the least time
the SELL projections' mathematics needs on this chip (``work/counts``)
over the device time of the SELL kernels."""

from _common import counts, decode_calls, decode_tokens, roofline
from harness.trace import kernel_seconds


def read(run):
    rows, calls = len(decode_tokens(run)), decode_calls(run)
    flops, nbytes = counts.sell_work(run["sizes"], rows, calls, run["elem"])
    return roofline(flops, nbytes, kernel_seconds(run["trace"], "decode",
                                                  "sell"), run["peak"])
