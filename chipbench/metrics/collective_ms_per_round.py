"""Device time of the collectives (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) on device 0 per round,
in milliseconds. Nothing to read on one chip."""
from chipbench import trace


def read(ctx):
    if not ctx.rounds:
        return None
    ns = trace.op_ns(ctx.device0, *ctx.window, trace.is_collective)
    return ns * 1e-6 / ctx.rounds if ns else None
