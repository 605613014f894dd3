"""Device time of the Pallas SCD kernel (``scd``) on device 0 per
round, in milliseconds."""
from chipbench import trace


def read(ctx):
    if not ctx.rounds:
        return None
    ns = trace.op_ns(ctx.device0, *ctx.window, trace.is_scd)
    return ns * 1e-6 / ctx.rounds if ns else None
