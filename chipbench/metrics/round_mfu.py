"""The whole round's share of the peaks of all the cell's chips, in %:
the least time one round's required work takes (``work.round_work``,
counted from the algorithm: every worker's column stream, w in and the
update out, the exchange and apply of the m-vector, the metric; the
all-reduce's interconnect bytes on more than one chip), times the
rounds, over the traced window. It bounds every kernel's gain."""
from chipbench import work


def read(ctx):
    if ctx.peaks is None or not ctx.rounds:
        return None
    ici = work.allreduce_ici_bytes(ctx.m, ctx.K) if ctx.chips > 1 else 0.0
    need = work.roofline_seconds(
        *work.round_work(ctx.m, ctx.H, ctx.n_local, ctx.K), ctx.peaks,
        chips=ctx.chips, ici_bytes=ici)
    return 100.0 * need * ctx.rounds / ctx.window_s
