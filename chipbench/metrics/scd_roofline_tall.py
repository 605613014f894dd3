"""The SCD kernel's share of its roofline, in %, in the tall cell,
where each column is longer than the kernel's VMEM holds and the
row-tiled form runs: the same reading as ``scd_roofline``, the least
time each ``scd`` call's required work takes at the chip's peaks
(``work.scd_call``: bytes-bound, the column stream), summed, over the
kernel's measured device time."""
from chipbench import trace, work


def read(ctx):
    if ctx.peaks is None:
        return None
    ns = trace.op_ns(ctx.device0, *ctx.window, trace.is_scd)
    calls = trace.count(ctx.device0, *ctx.window, trace.is_scd)
    if not ns:
        return None
    need = calls * work.roofline_seconds(
        *work.scd_call(ctx.m, ctx.H, ctx.n_local), ctx.peaks)
    return 100.0 * need / (ns * 1e-9)
