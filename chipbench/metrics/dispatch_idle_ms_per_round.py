"""Device-0 idle time inside the trainer's ``repro.dispatch`` spans
(the host key split and the round's enqueue), each solve's first
excepted, per round, in milliseconds."""
from chipbench import spans


def read(ctx):
    ms = spans.idle_ms(ctx, "dispatch")
    return None if ms is None else ms / spans.rounds(ctx)
