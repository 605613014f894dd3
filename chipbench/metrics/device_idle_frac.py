"""Share of the traced window in which no operation ran on device 0:
1 - (union of its operations' intervals) / window. The record loop
and the host between rounds set it."""
from chipbench import trace


def read(ctx):
    if not ctx.device0:
        return None
    lo, hi = ctx.window
    return 1.0 - trace.busy_ns(ctx.device0, lo, hi) / (hi - lo)
